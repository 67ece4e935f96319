"""Table 2: scheduling overhead per data item vs number of nodes, plus
the batched `place_many` amortization the engine adds on top.

Per-item numbers run the scheduler through a non-committing
:class:`PlacementEngine` (pure decision cost, matching the paper's
Table 2 protocol).  The batched section places a >=100-item batch twice
on identical clusters — sequential ``place`` vs ``place_many`` with a
shared :class:`BatchContext` — verifies the placements are identical,
and reports the speedup (the reliability-DP reuse of §4.4's frontier).

The ``batched_sc`` section isolates the jitted D-Rex SC window-scoring
kernel (repro.core.sc_kernel): the scalar numpy oracle
(``DRexSC.place_scalar``) vs the vectorized ``place_many`` path, both
non-committing (one vmapped call over the whole queue) and committing
(per-item kernel calls, since every commit invalidates the remaining
scores).  Decisions are verified identical before speedups are reported.

The ``batched_greedy`` section applies the same protocol to the greedy
kernels (repro.core.greedy_kernel) at ``greedy_nodes`` nodes — the
GreedyMinStorage decision-cost column is the headline number the
benchmark-regression gate (benchmarks/gate.py) protects.

The ``first_decision`` section (stamped before any other section warms
a kernel) times the process's cold first batched placement — the XLA
compile, or a read from the persistent compilation cache
(repro.core.jitcache) — against the in-process warm repeat, with the
jit-cache status alongside.

The ``batched_lb`` section does the same for the D-Rex LB kernel
(repro.core.lb_kernel) at ``n_nodes`` and again at ``greedy_nodes``
nodes; its decision-cost speedup is gated alongside SC's.  The section
also stamps the shared shape-bucket compile-cache census
(``repro.telemetry.snapshot().compile_cache``) so recompile counts are
visible in the emitted telemetry.
"""

import time

import numpy as np

from repro import telemetry
from repro.core import (
    BatchContext,
    ClusterView,
    DataItem,
    PlacementEngine,
    StorageNode,
    create_scheduler,
)

from .common import csv_row, emit


def _cluster(n: int) -> ClusterView:
    rng = np.random.default_rng(n)
    nodes = [
        StorageNode(
            node_id=i,
            capacity_mb=float(rng.uniform(5e6, 2e7)),
            write_bw=float(rng.uniform(100, 250)),
            read_bw=float(rng.uniform(100, 400)),
            annual_failure_rate=float(rng.uniform(0.003, 0.05)),
        )
        for i in range(n)
    ]
    return ClusterView.from_nodes(nodes)


ADAPTIVE = ("greedy_min_storage", "greedy_least_used", "drex_lb", "drex_sc")


def _first_decision(n_nodes: int, batch: int, lines: list[str]) -> dict:
    """Cold-vs-warm first-decision latency (must run before any other
    section jits a kernel, while the process is genuinely cold).

    Cold = the process's first batched placement, which pays the XLA
    compile — from source, or from the persistent disk cache
    (repro.core.jitcache) when a previous process already compiled the
    same bucketed signature.  Warm = the same call on a fresh engine,
    served by the in-process jit cache.  The stamped ``jit_cache``
    status gives the cache directory and its entry count.
    """
    items = [DataItem(i, 117.0, float(i), 365.0, 0.999) for i in range(batch)]
    t0 = time.perf_counter()
    PlacementEngine(_cluster(n_nodes), "greedy_least_used").place_many(items)
    cold_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    PlacementEngine(_cluster(n_nodes), "greedy_least_used").place_many(items)
    warm_ms = (time.perf_counter() - t0) * 1e3
    lines.append(csv_row("table2_first_decision_cold", cold_ms * 1e3, f"nodes={n_nodes}"))
    lines.append(csv_row("table2_first_decision_warm", warm_ms * 1e3, f"nodes={n_nodes}"))
    return {
        "n_nodes": n_nodes,
        "batch": batch,
        "cold_ms": cold_ms,
        "warm_ms": warm_ms,
        "jit_cache": telemetry.snapshot().jit_cache,
    }


def run(
    sizes=(10, 50, 100, 500),
    reps: int = 3,
    batch: int = 128,
    greedy_nodes: int = 500,
    greedy_batch: int = 32,
) -> list[str]:
    lines = []
    table = {}
    table["first_decision"] = _first_decision(greedy_nodes, greedy_batch, lines)
    for algo in ADAPTIVE:
        table[algo] = {}
        for n in sizes:
            engine = PlacementEngine(_cluster(n), algo, auto_commit=False)
            item = DataItem(0, 117.0, 0.0, 365.0, 0.999)
            engine.place(item)  # warm
            r = 1 if n >= 500 else reps
            t0 = time.perf_counter()
            for _ in range(r):
                engine.place(item)
            per_item_ms = (time.perf_counter() - t0) / r * 1e3
            table[algo][n] = per_item_ms
            lines.append(csv_row(f"table2_{algo}_n{n}", per_item_ms * 1e3, f"nodes={n}"))

    # -- batched amortization (place_many vs sequential place) ---------------
    table["batched"] = {}
    n_nodes = 100
    items = [DataItem(i, 117.0, float(i), 365.0, 0.999) for i in range(batch)]
    for algo in ADAPTIVE:
        seq = PlacementEngine(_cluster(n_nodes), algo)
        t0 = time.perf_counter()
        seq_records = [seq.place(it) for it in items]
        t_seq = time.perf_counter() - t0

        bat = PlacementEngine(_cluster(n_nodes), algo)
        ctx = BatchContext()
        t0 = time.perf_counter()
        bat_records = bat.place_many(items, ctx=ctx)
        t_bat = time.perf_counter() - t0

        if [r.placement for r in seq_records] != [r.placement for r in bat_records]:
            raise AssertionError(f"{algo}: place_many diverged from sequential place")
        speedup = t_seq / t_bat if t_bat > 0 else float("inf")
        table["batched"][algo] = {
            "n_nodes": n_nodes,
            "batch": batch,
            "sequential_ms_per_item": t_seq / batch * 1e3,
            "batched_ms_per_item": t_bat / batch * 1e3,
            "speedup": speedup,
            "ctx_hits": ctx.hits,
            "ctx_misses": ctx.misses,
        }
        lines.append(
            csv_row(
                f"table2_{algo}_batch{batch}",
                t_bat / batch * 1e6,
                f"amortization={speedup:.2f}x",
            )
        )

    # -- D-Rex SC: scalar numpy oracle vs jitted/vmapped kernel --------------
    table["batched_sc"] = _sc_scalar_vs_vectorized(n_nodes, batch, lines)

    # -- greedy kernels: scalar oracles vs jitted/vmapped kernels ------------
    table["batched_greedy"] = _greedy_scalar_vs_vectorized(
        greedy_nodes, greedy_batch, lines
    )

    # -- D-Rex LB: scalar numpy oracle vs jitted/vmapped kernel --------------
    table["batched_lb"] = _lb_scalar_vs_vectorized(
        n_nodes, batch, greedy_nodes, greedy_batch, lines
    )
    emit("table2", table)
    return lines


def _sc_scalar_vs_vectorized(n_nodes: int, batch: int, lines: list[str]) -> dict:
    """Scalar-oracle vs vectorized-kernel scheduling overhead for SC.

    Non-committing engines score the whole queue against one snapshot
    (a single vmapped call); committing engines re-score after every
    commit (per-item kernel calls).  Both are verified decision-
    identical to the sequential scalar oracle before timing counts.
    """
    from .common import scalar_vs_vectorized

    items = [DataItem(i, 117.0, float(i), 365.0, 0.999) for i in range(batch)]
    out = {"n_nodes": n_nodes, "batch": batch}
    for label, auto_commit in (("decision_cost", False), ("committed", True)):
        cols = scalar_vs_vectorized(
            lambda: PlacementEngine(
                _cluster(n_nodes), create_scheduler("drex_sc"), auto_commit=auto_commit
            ),
            items,
        )
        out[label] = cols
        lines.append(
            csv_row(
                f"table2_drex_sc_{label}_vectorized",
                cols["vectorized_ms_per_item"] * 1e3,
                f"scalar_vs_vectorized={cols['speedup_vs_scalar']:.2f}x",
            )
        )
    return out


def _lb_scalar_vs_vectorized(
    n_nodes: int, batch: int, big_nodes: int, big_batch: int, lines: list[str]
) -> dict:
    """Scalar-oracle vs vectorized-kernel scheduling overhead for D-Rex
    LB (repro.core.lb_kernel), at the standard 100-node point and again
    at the greedy section's large-cluster point.

    Decision cost (``auto_commit=False``) scores the whole queue in
    ~one vmapped call — the Table-2 protocol and the gated metric.  The
    committed column is honest about LB's conservative rescoring: its
    balance penalty depends on the cluster-wide mean free space, so
    every commit invalidates the remaining scores and the engine
    degrades to per-item calls (which dispatch to the kernel only above
    ``DRexLB.KERNEL_MIN_NODES`` live nodes).
    """
    from .common import scalar_vs_vectorized

    out = {}
    points = (("standard", n_nodes, batch), ("large", big_nodes, big_batch))
    for point, label_nodes, label_batch in points:
        items = [
            DataItem(i, 117.0, float(i), 365.0, 0.999)
            for i in range(label_batch)
        ]
        cols_n = {"n_nodes": label_nodes, "batch": label_batch}
        for label, auto_commit in (("decision_cost", False), ("committed", True)):
            cols = scalar_vs_vectorized(
                lambda: PlacementEngine(
                    _cluster(label_nodes), create_scheduler("drex_lb"),
                    auto_commit=auto_commit,
                ),
                items,
            )
            cols_n[label] = cols
            lines.append(
                csv_row(
                    f"table2_drex_lb_{label}_n{label_nodes}_vectorized",
                    cols["vectorized_ms_per_item"] * 1e3,
                    f"scalar_vs_vectorized={cols['speedup_vs_scalar']:.2f}x",
                )
            )
        out[point] = cols_n
    # Recompile census for the whole table2 run (all kernels share the
    # shapes bucketer; see tests/test_shapes.py for the churn budget).
    out["compile_cache"] = telemetry.snapshot().compile_cache
    return out


def _greedy_scalar_vs_vectorized(n_nodes: int, batch: int, lines: list[str]) -> dict:
    """Scalar oracles vs the jitted greedy kernels (repro.core.greedy_kernel).

    Same protocol as the SC section: non-committing engines score the
    whole queue against one snapshot (decision cost — the Table-2
    protocol and the metric the benchmark-regression gate watches);
    committing engines re-score after every commit.  Decisions are
    verified identical to the sequential scalar oracle before any
    speedup is reported.
    """
    from .common import scalar_vs_vectorized

    items = [DataItem(i, 117.0, float(i), 365.0, 0.999) for i in range(batch)]
    out = {}
    for algo in ("greedy_min_storage", "greedy_least_used"):
        cols_algo = {"n_nodes": n_nodes, "batch": batch}
        for label, auto_commit in (("decision_cost", False), ("committed", True)):
            cols = scalar_vs_vectorized(
                lambda: PlacementEngine(
                    _cluster(n_nodes), create_scheduler(algo),
                    auto_commit=auto_commit,
                ),
                items,
            )
            cols_algo[label] = cols
            lines.append(
                csv_row(
                    f"table2_{algo}_{label}_vectorized",
                    cols["vectorized_ms_per_item"] * 1e3,
                    f"scalar_vs_vectorized={cols['speedup_vs_scalar']:.2f}x",
                )
            )
        out[algo] = cols_algo
    return out
