"""On-chip smoke test of the storage system's main path (one TPU chip).

    python chip_smoke.py [--seed N]

Runs in one process and starts no other.  It refuses to run anywhere
but a TPU: a CPU timing or a CPU decision says nothing about the chip.
Three phases, each checked against the repository's own references:

1. **paper** — the paper's four 10-node sets at published capacity,
   each under a Table-3 ``meva`` trace, streamed through
   ``PlacementFrontier`` for D-Rex SC, D-Rex LB, GreedyMinStorage and
   GreedyLeastUsed.  The default dispatch crossovers send 10-node
   decisions to the numpy oracle, so this phase forces the decision
   kernels (``KERNEL_MIN_NODES = KERNEL_MIN_NODES_BATCH = 0``) to run
   the paper's cells.
2. **scale** — a 10k-node heterogeneous cluster
   (``benchmarks.scale_cluster.synthetic_cluster``), one ``place_many``
   batch per pre-filtered scheduler under the default dispatch policy.
3. **checkpoint** — a ``DRexCheckpointer`` over the Chameleon NVMe+HDD
   testbed (paper §6 Table 5) saves a >= 1 GiB pytree of seeded arrays,
   loses two storage nodes, and restores; the coding runs through the
   Pallas kernel.

Every decision of phases 1-2 is compared with the scalar oracle
(``place_scalar``, run by a twin engine whose scheduler has
``use_kernel = False``); restored leaves must be bit-exact, and sampled
parity is checked against the host reference ``ops.encode_chunks_ref``
on the CPU device.  The float64 decision kernels run on the device
``repro.core.shapes.decision_scope`` names (the host CPU: the TPU's
emulated float64 does not round as numpy does) and the coding kernel on
the TPU; the run prints where each kernel's outputs landed and fails if
that is anywhere else.  Timings printed here are host wall-clock seconds.
The last line of standard output is one JSON object; it says
``"ok": true`` only when every phase passed, and the exit status is
nonzero otherwise.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: paper phase: items per (node set, scheduler) and their arrival rate.
#: 2000 items/s fills the frontier's 32-item windows well inside its
#: 50 ms flush deadline, so windows are full micro-batches.
PAPER_ITEMS = 128
PAPER_RATE_PER_S = 2000.0
PAPER_SCHEDULERS = ("drex_sc", "drex_lb", "greedy_min_storage", "greedy_least_used")
NODE_SETS = ("most_used", "most_unreliable", "most_reliable", "homogeneous")

#: scale phase: cluster size and the one batch size every scheduler uses.
SCALE_NODES = 10_000
SCALE_BATCH = 8
SCALE_SCHEDULERS = ("drex_sc", "drex_lb", "greedy_least_used")

#: checkpoint phase: leaves x bytes per leaf (4 x 256 MiB = 1 GiB).
CKPT_LEAVES = 4
CKPT_LEAF_BYTES = 256 << 20
#: parity columns checked against the host reference, per sampled group.
PARITY_SAMPLE_BYTES = 1 << 16

#: kernel census names -> the module each belongs to.
DECISION_KERNELS = {
    "sc_kernel": "sc_kernel",
    "lb_kernel": "lb_kernel",
    "least_used_kernel": "greedy_kernel",
    "min_storage_kernel": "greedy_kernel",
}


class CompileLog:
    """Backend-compile seconds and persistent-cache hits per phase label,
    from jax's own monitoring events."""

    def __init__(self):
        self.label = "setup"
        self.by_label: dict[str, dict[str, float]] = {}

    def _slot(self) -> dict[str, float]:
        return self.by_label.setdefault(
            self.label, {"compile_s": 0.0, "compiles": 0, "cache_hits": 0}
        )

    def on_duration(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            slot = self._slot()
            slot["compile_s"] += secs
            slot["compiles"] += 1

    def on_event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self._slot()["cache_hits"] += 1

    @contextlib.contextmanager
    def phase(self, label: str):
        prev, self.label = self.label, label
        try:
            yield
        finally:
            self.label = prev


def _records_equal(a, b) -> bool:
    return (a.ok, a.placement, a.reason) == (b.ok, b.placement, b.reason)


def _force_kernel(sched):
    sched.KERNEL_MIN_NODES = 0
    sched.KERNEL_MIN_NODES_BATCH = 0
    return sched


def _oracle(sched):
    sched.use_kernel = False
    return sched


def paper_phase(seed: int, n_items: int = PAPER_ITEMS, log: CompileLog | None = None):
    """Frontier streams on the paper's node sets; returns (compared,
    matched, kernel_calls)."""
    from repro.core import ClusterView, DataItem, PlacementEngine, create_scheduler
    from repro.core import shapes
    from repro.serve.placement import PlacementFrontier, arrival_events
    from repro.storage import make_node_set, make_trace

    trace = make_trace("meva", seed=seed, n_items=n_items)
    # Table-3 sizes and random-nines targets; arrivals re-timed to an
    # open-loop rate that fills the frontier's windows.
    items = [
        DataItem(it.item_id, it.size_mb, it.item_id / PAPER_RATE_PER_S,
                 it.delta_t_days, it.reliability_target)
        for it in trace
    ]
    compared = matched = 0
    calls0 = _census_calls(shapes)
    for node_set in NODE_SETS:
        for name in PAPER_SCHEDULERS:
            outcomes = []
            for make in (_force_kernel, _oracle):
                cluster = ClusterView.from_nodes(make_node_set(node_set))
                engine = PlacementEngine(cluster, make(create_scheduler(name)))
                ctx = log.phase(f"paper/{name}") if (log and make is _force_kernel) \
                    else contextlib.nullcontext()
                with ctx:
                    report = PlacementFrontier(engine).run(arrival_events(items))
                outcomes.append(report.outcomes)
            got, want = outcomes
            same = [
                (a.item_id, a.status, a.placement) == (b.item_id, b.status, b.placement)
                for a, b in zip(got, want)
            ]
            n_ok = sum(same)
            compared += len(want)
            matched += n_ok if len(got) == len(want) else 0
            placed = sum(o.ok for o in got)
            print(f"  paper {node_set:16s} {name:20s} decisions={len(want)} "
                  f"placed={placed} match_oracle={n_ok}/{len(want)}", flush=True)
    return compared, matched, _census_delta(shapes, calls0)


def scale_phase(seed: int, n_nodes: int = SCALE_NODES, batch: int = SCALE_BATCH,
                log: CompileLog | None = None):
    """One place_many batch per pre-filtered scheduler on a 10k-node
    cluster; returns (compared, matched, kernel_calls)."""
    from benchmarks.scale_cluster import synthetic_cluster
    from repro.core import PlacementEngine, create_scheduler, shapes
    from repro.storage import make_trace

    items = make_trace("meva", seed=seed + 1, n_items=batch)
    compared = matched = 0
    calls0 = _census_calls(shapes)
    for name in SCALE_SCHEDULERS:
        t0 = time.perf_counter()
        ctx = log.phase(f"scale/{name}") if log else contextlib.nullcontext()
        with ctx:
            got = PlacementEngine(
                synthetic_cluster(n_nodes, seed), name
            ).place_many(items)
        t_kernel = time.perf_counter() - t0
        t0 = time.perf_counter()
        want = PlacementEngine(
            synthetic_cluster(n_nodes, seed), _oracle(create_scheduler(name))
        ).place_many(items)
        t_oracle = time.perf_counter() - t0
        n_ok = sum(_records_equal(a, b) for a, b in zip(got, want))
        compared += len(want)
        matched += n_ok
        print(f"  scale {n_nodes} nodes {name:20s} decisions={len(want)} "
              f"placed={sum(r.ok for r in got)} match_oracle={n_ok}/{len(want)} "
              f"host_wall_s kernel_path={t_kernel:.3f} oracle={t_oracle:.3f}",
              flush=True)
    return compared, matched, _census_delta(shapes, calls0)


def checkpoint_phase(seed: int, n_leaves: int = CKPT_LEAVES,
                     leaf_bytes: int = CKPT_LEAF_BYTES,
                     log: CompileLog | None = None):
    """Save >= 1 GiB, kill two nodes, restore bit-exact; returns a dict
    of results (``ok`` is the verdict)."""
    import jax
    import numpy as np

    from repro.checkpoint import CheckpointPolicy, DRexCheckpointer, StorageFabric
    from repro.kernels import ops
    from repro.storage.nodesets import chameleon_nodes

    rng = np.random.default_rng(seed)
    dtypes = (np.float32, np.uint8, np.int32, np.float16)
    state = {}
    for i in range(n_leaves):
        dt = np.dtype(dtypes[i % len(dtypes)])
        raw = rng.integers(0, 256, size=leaf_bytes, dtype=np.uint8)
        state[f"leaf{i}"] = raw.view(dt)
    raw_bytes = sum(a.nbytes for a in state.values())

    fabric = StorageFabric(chameleon_nodes())
    ck = DRexCheckpointer(
        fabric, "drex_sc",
        CheckpointPolicy(reliability_target=0.99999, retention_days=365.0),
    )
    ctx = log.phase("checkpoint/save") if log else contextlib.nullcontext()
    t0 = time.perf_counter()
    with ctx:
        manifest = ck.save(state, step=1)
    t_save = time.perf_counter() - t0
    groups = [g for leaf in manifest["leaves"] for g in leaf["groups"]]

    # Parity sample against the host reference, before any failure.
    cpu = jax.devices("cpu")[0]
    parity_ok = True
    for g in (groups[0], groups[-1]):
        chunks = np.stack([
            np.frombuffer(fabric.get(node, f"{g['key']}_r{row}"), dtype=np.uint8)
            for row, node in enumerate(g["node_ids"])
        ])
        k = g["k"]
        cols = slice(0, min(PARITY_SAMPLE_BYTES, chunks.shape[1]))
        with jax.default_device(cpu):
            want = np.asarray(ops.encode_chunks_ref(chunks[:k, cols], g["p"]))
        parity_ok &= bool(np.array_equal(chunks[k:, cols], want))

    # Lose the two nodes holding the most chunks.
    load: dict[int, int] = {}
    for g in groups:
        for node in g["node_ids"]:
            load[node] = load.get(node, 0) + 1
    victims = sorted(load, key=lambda n: (-load[n], n))[:2]
    for node in victims:
        ck.on_node_failure(node)
    lost_per_group = [sum(n in victims for n in g["node_ids"]) for g in groups]

    ctx = log.phase("checkpoint/restore") if log else contextlib.nullcontext()
    t0 = time.perf_counter()
    with ctx:
        restored = ck.restore(1, state)
    t_restore = time.perf_counter() - t0
    # Compare bytes, not values: seeded bytes viewed as floats hold NaNs.
    exact = all(
        restored[k].dtype == v.dtype and restored[k].shape == v.shape
        and np.array_equal(np.asarray(restored[k]).view(np.uint8), v.view(np.uint8))
        for k, v in state.items()
    )
    return {
        "ok": bool(exact and parity_ok),
        "raw_gib": raw_bytes / 2**30,
        "stored_gib": ck.stats["bytes_stored"] / 2**30,
        "groups": len(groups),
        "codes": sorted({(g["k"], g["p"]) for g in groups}),
        "killed_nodes": victims,
        "max_chunks_lost_per_group": max(lost_per_group),
        "parity_matches_host_reference": parity_ok,
        "restored_bit_exact": exact,
        "host_wall_s_save": t_save,
        "host_wall_s_restore": t_restore,
    }


def _census_calls(shapes) -> dict[str, int]:
    return {k: v["calls"] for k, v in shapes.compile_cache_stats()["kernels"].items()}


def _census_delta(shapes, before: dict[str, int]) -> dict[str, int]:
    after = _census_calls(shapes)
    return {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (jax platform {dev.platform!r}); "
              "refusing to run on anything but the chip", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(f"device: {device}", flush=True)

    import jax.monitoring

    from repro.core import jitcache, shapes

    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)
    print(f"compile cache: {jitcache.status()}", flush=True)

    verdicts = {}
    t0 = time.perf_counter()
    print("phase paper:", flush=True)
    compared, matched, calls = paper_phase(args.seed, log=log)
    print(f"  decisions compared={compared} matched={matched} kernel_calls={calls}")
    verdicts["paper"] = compared > 0 and matched == compared and bool(calls)

    print("phase scale:", flush=True)
    compared, matched, calls = scale_phase(args.seed, log=log)
    print(f"  decisions compared={compared} matched={matched} kernel_calls={calls}")
    verdicts["scale"] = compared > 0 and matched == compared and bool(calls)

    print("phase checkpoint:", flush=True)
    ck = checkpoint_phase(args.seed, log=log)
    for key, val in ck.items():
        print(f"  {key}: {val}")
    verdicts["checkpoint"] = ck["ok"] and ck["raw_gib"] >= 1.0

    census = shapes.compile_cache_stats()["kernels"]
    ran_on = shapes.kernel_devices()
    print("compile census (signatures / calls) and device per kernel:")
    for name in sorted(census):
        sigs = sorted(shapes.issued_shapes(name), key=repr)
        module = DECISION_KERNELS.get(name, "kernels.rs_bitmatmul")
        print(f"  {name} [{module}] ran on {','.join(ran_on.get(name, ()))} "
              f"signatures={len(sigs)} calls={census[name]['calls']}")
        for sig in sigs:
            print(f"    {sig}")
    pallas = [s for s in shapes.issued_shapes("rs_bitmatmul") if s[-1] == "pallas"]
    verdicts["census"] = set(DECISION_KERNELS) <= set(census) and bool(pallas)
    verdicts["devices"] = all(
        ran_on.get(name) and all(
            d.split(":")[0] == shapes.DECISION_PLATFORM for d in ran_on[name]
        )
        for name in DECISION_KERNELS
    ) and ran_on.get("rs_bitmatmul") == (f"{dev.platform}:{dev.id}",)
    print("backend compile seconds by phase (host wall clock; "
          "includes persistent-cache reads):")
    for label, slot in sorted(log.by_label.items()):
        print(f"  {label}: compile_s={slot['compile_s']:.3f} "
              f"compiles={slot['compiles']} cache_hits={slot['cache_hits']}")
    print(f"compile cache after run: {jitcache.status()}")
    print(f"total host wall seconds: {time.perf_counter() - t0:.3f}")
    print(f"verdicts: {verdicts}", flush=True)
    if not all(verdicts.values()):
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
