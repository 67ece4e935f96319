PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

## Opt-in 100k-node XL smoke lane (benchmarks/scale_cluster.py "xl"
## section): off by default so the fast lanes stay fast; the gate
## reports the xl.* metrics as skipped when the section is absent.
## Enable per-invocation with `make bench-scale SCALE_XL=1` (nightly CI
## runs with it set unconditionally).  Regenerate baselines with the
## flag set (`make bench-baseline SCALE_XL=1`) so the gated xl digests
## exist to compare against.
SCALE_XL ?=
export SCALE_XL

.PHONY: verify test test-fast smoke-bench bench-check bench-baseline bench-serve bench-ec bench-scale

## Tier-1 gate: full test suite + smoke runs of the scheduling-overhead
## benchmark (batched place_many end to end), the Fig. 12 failure
## benchmark (event-driven failure/repair path incl. finite repair bw),
## the sustained-load placement-service lane (serve_load), and the
## batched-EC data plane / pipelined checkpoint lanes (fig1, fig13).
verify: test smoke-bench

test:
	$(PYTHON) -m pytest -x -q

## Quick-feedback lane (< 30 s): everything except the @pytest.mark.slow
## checkpoint/e2e/simulator sweeps — covers the reliability kernel, schedulers, engine,
## SC-kernel equivalence, invariant suite, simulator and traces.
test-fast:
	$(PYTHON) -m pytest -x -q -m "not slow"

## Smoke sweeps write to a gitignored scratch directory so `make verify`
## never clobbers the committed full-sweep JSON in results/benchmarks/.
smoke-bench:
	$(PYTHON) -m benchmarks.run --only table2,fig12,serve_load,fig1,fig13,scale --smoke \
		--out results/benchmarks/ci-smoke

## Fast lane for the streaming placement service alone: the open-loop
## Poisson sustained-load sweep (goodput, p50/p99 decision latency,
## queue depth, reject rate) gated against its committed smoke baseline.
bench-serve:
	$(PYTHON) -m benchmarks.run --only serve_load --smoke \
		--out results/benchmarks/ci-smoke \
		--check-against results/benchmarks/smoke

## Fast lane for the erasure-coding data plane alone: fig1's batched
## cohort-vs-per-item encode (digest + speedup + compile census) and
## fig13's pipelined-vs-serial checkpoint upload, gated against the
## committed smoke baselines.
bench-ec:
	$(PYTHON) -m benchmarks.run --only fig1,fig13 --smoke \
		--out results/benchmarks/ci-smoke \
		--check-against results/benchmarks/smoke

## Fast lane for the cluster-scale axis alone: the 10k-node top-M
## pre-filter lane (filtered-vs-unfiltered decision-cost speedups,
## bit-exactness, pre-filter hit rate, >= 5x acceptance floor), gated
## against its committed smoke baseline.  Add SCALE_XL=1 to grow the
## oracle-free 100k lane (placement digests, argsort-path bit-exactness
## replay, tracker hit-rate floor, within-2x-of-10k cost ceiling).
bench-scale:
	$(PYTHON) -m benchmarks.run --only scale --smoke \
		--out results/benchmarks/ci-smoke \
		--check-against results/benchmarks/smoke

## Benchmark-regression gate: run the CI-sized sweeps into the scratch
## directory and fail if any gated decision-cost metric regressed >20%
## against the committed smoke baselines (results/benchmarks/smoke/).
## Regenerate baselines with:
##   $(PYTHON) -m benchmarks.run --only table2,fig12,serve_load,fig1,fig13,scale --smoke --out results/benchmarks/smoke
bench-check:
	$(PYTHON) -m benchmarks.run --only table2,fig12,serve_load,fig1,fig13,scale --smoke \
		--out results/benchmarks/ci-smoke \
		--check-against results/benchmarks/smoke

## Regenerate the committed smoke baselines the gate compares against
## (results/benchmarks/smoke/).  Run after an intentional perf change,
## an intentional behavior change to the fig12 equality-gated retained
## fractions or the fig1/fig13 digests, or when rebasing the gate onto
## a new machine class — then review and commit the JSON diff.  Full
## workflow: benchmarks/README.md.
bench-baseline:
	$(PYTHON) -m benchmarks.run --only table2,fig12,serve_load,fig1,fig13,scale --smoke \
		--out results/benchmarks/smoke
