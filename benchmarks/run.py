"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the harness contract, and
writes per-figure JSON (stamped with ``meta``: schema version, git SHA,
smoke flag) into results/benchmarks/.

    PYTHONPATH=src python -m benchmarks.run [--only fig5,fig12] [--smoke]
        [--out DIR] [--check-against BASELINE_DIR]

``--smoke`` shrinks the parameterizable benchmarks to CI-sized sweeps;
used by ``make verify`` / the GitHub Actions workflow.  All RNGs are
seeded explicitly at startup so repeated runs are comparable.

``--check-against`` is the benchmark-regression gate (``make
bench-check`` / the ``bench-gate`` CI job): after the run, the freshly
emitted JSON is compared like-for-like against the committed baselines
in BASELINE_DIR (see benchmarks/gate.py) and the process exits nonzero
if any gated decision-cost metric regressed beyond the budget.
"""

from __future__ import annotations

import argparse
import random
import sys
import time

import numpy as np

from . import (
    fig1_encode_breakdown,
    fig5_reliability_sweep,
    fig6_saturation,
    fig7_nodesets,
    fig8_throughput,
    fig9_op_breakdown,
    fig10_datasets,
    fig11_throughput_datasets,
    fig12_failures,
    fig13_e2e_checkpoint,
    gate,
    scale_cluster,
    serve_load,
    table2_overhead,
)
from . import common

BENCHES = {
    "fig1": fig1_encode_breakdown.run,
    "table2": table2_overhead.run,
    "fig5": fig5_reliability_sweep.run,
    "fig6": fig6_saturation.run,
    "fig7": fig7_nodesets.run,
    "fig8": fig8_throughput.run,
    "fig9": fig9_op_breakdown.run,
    "fig10": fig10_datasets.run,
    "fig11": fig11_throughput_datasets.run,
    "fig12": fig12_failures.run,
    "fig13": fig13_e2e_checkpoint.run,
    "serve_load": serve_load.run,
    "scale": scale_cluster.run,
}


#: reduced parameters per benchmark under --smoke (others run unchanged).
SMOKE_KWARGS = {
    # Batched-EC data plane lane: small per-K sweep, but a cohort big
    # enough that the gated per-item-vs-batched ratio divides dispatch
    # overhead x n_groups, not timer noise.
    "fig1": dict(size_mb=1.0, ks=(2, 4, 6), reps=2, n_groups=32, group_kb=16),
    # Pipelined-vs-serial checkpoint upload on a CI-sized synthetic
    # state; link_mbps stays at the default so the put cost (what the
    # pipeline overlaps) is the same regime as the full run.
    "fig13": dict(n_items=16, item_kb=128, reps=3,
                  algos=("drex_sc", "ec(3,2)")),
    # greedy_batch stays >= 32 so the gated speedup ratios divide two
    # multi-millisecond totals (min-of-reps timed) instead of dispatch
    # jitter; see benchmarks/gate.py.
    "table2": dict(
        sizes=(10, 50), reps=1, batch=100, greedy_nodes=100, greedy_batch=32
    ),
    # CI-sized failure/repair sweep: exercises the event-driven simulator's
    # failure, repair-bandwidth and drop paths on every PR.
    "fig12": dict(
        rts=(0.9,),
        failures=(2, 5),
        repair_bws=(float("inf"), 0.01),
        sweep_algos=("drex_sc", "ec(3,2)"),
        algos=("drex_sc", "drex_lb", "ec(3,2)"),
    ),
    # Sustained-load placement-service lane: one reject-free rate (oracle
    # checked against the sequential baseline) and one overload rate
    # (deterministic backpressure), kept small enough for the PR lane.
    "serve_load": dict(n_items=240, rates=(60.0, 1500.0), reps=2),
    # Cluster-axis scale lane: the node count stays at 10k even under
    # --smoke (the pre-filter's >= 5x acceptance floor is only meaningful
    # at scale); the unfiltered reference path is what costs seconds, so
    # smoke trims reps, not N.
    "scale": dict(reps=2),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset")
    ap.add_argument("--smoke", action="store_true", help="CI-sized sweeps")
    ap.add_argument(
        "--out",
        default=None,
        help="directory for emitted JSON (default results/benchmarks)",
    )
    ap.add_argument(
        "--check-against",
        default=None,
        metavar="BASELINE_DIR",
        help="after running, fail (exit 1) if any gated decision-cost "
        "metric regressed beyond the budget vs the baselines in this dir",
    )
    args = ap.parse_args()
    # Explicit global seeding: every benchmark already uses per-call
    # default_rng(seed), but any stray library draw must be repeatable
    # too or the regression gate would not compare like-for-like.
    random.seed(0)
    np.random.seed(0)
    common.set_run_context(smoke=args.smoke, out_dir=args.out)
    names = list(BENCHES) if not args.only else args.only.split(",")
    print("name,us_per_call,derived")
    failures = []
    for name in names:
        t0 = time.perf_counter()
        kwargs = SMOKE_KWARGS.get(name, {}) if args.smoke else {}
        try:
            for line in BENCHES[name](**kwargs):
                print(line, flush=True)
        except Exception as e:  # keep the harness running, report at exit
            failures.append((name, repr(e)))
            print(f"{name},0.0,ERROR:{type(e).__name__}", flush=True)
        print(f"{name}_wall,{(time.perf_counter()-t0)*1e6:.0f},", flush=True)
    gate_failed = False
    if args.check_against:
        out_dir = args.out or common.RESULTS
        regressions, notes = gate.check_against(
            out_dir, args.check_against, names
        )
        gate.report(regressions, notes)
        gate_failed = bool(regressions)
    if failures:
        for n, e in failures:
            print(f"[bench] FAILED {n}: {e}", file=sys.stderr)
    if failures or gate_failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
