"""KERNEL_MIN_NODES dispatch-boundary coverage (SC + greedy kernels).

Each vectorized scheduler dispatches single-item ``place`` calls to its
jitted kernel only at/above a crossover cluster size
(``KERNEL_MIN_NODES``); below it the scalar numpy oracle wins on
dispatch overhead.  Whatever the constant's value, decisions must be
identical on both sides of the boundary — these tests pin that at
``N - 1``, ``N`` and ``N + 1`` live nodes for every kernel-backed
scheduler, and assert the dispatch itself flips exactly at ``N``.

``greedy_least_used`` runs with an overridden boundary: its class
default intentionally exceeds any realistic cluster (the scalar
first-feasible-N scan is dispatch-proof), which would make the
parametrized cluster sizes impractical.
"""

import numpy as np
import pytest

from repro.core import ClusterView, DataItem, StorageNode, create_scheduler
from repro.core import greedy_kernel, lb_kernel, sc_kernel


#: (scheduler, boundary override or None for the class default,
#:  kernel module, batch entry point the spy wraps)
#: drex_lb also runs overridden: its class default (~the measured 200+
#: node crossover against its vectorized-numpy oracle) would make the
#: parametrized cluster sizes slow for a boundary check.
CASES = [
    ("drex_sc", None, sc_kernel, "score_windows_batch"),
    ("greedy_min_storage", None, greedy_kernel, "min_storage_batch"),
    ("greedy_least_used", 12, greedy_kernel, "least_used_batch"),
    ("drex_lb", 16, lb_kernel, "lb_batch"),
]


def boundary_cluster(n: int, seed: int = 0) -> ClusterView:
    rng = np.random.default_rng(seed)
    return ClusterView.from_nodes(
        [
            StorageNode(
                node_id=i,
                capacity_mb=float(rng.uniform(2e3, 1e5)),
                write_bw=float(rng.uniform(50, 400)),
                read_bw=float(rng.uniform(50, 450)),
                annual_failure_rate=float(rng.uniform(0.001, 0.1)),
                used_mb=float(rng.uniform(0.0, 1e3)),
            )
            for i in range(n)
        ]
    )


def boundary_items(count: int = 4):
    rng = np.random.default_rng(1)
    targets = [0.9, 0.99, 0.999]
    return [
        DataItem(i, float(rng.uniform(1.0, 400.0)), float(i),
                 float(rng.uniform(30.0, 730.0)),
                 targets[int(rng.integers(len(targets)))])
        for i in range(count)
    ]


@pytest.mark.parametrize("delta", [-1, 0, 1])
@pytest.mark.parametrize("name,override,module,entry", CASES)
class TestDispatchBoundary:
    def _make(self, name, override):
        sched = create_scheduler(name)
        if override is not None:
            sched.KERNEL_MIN_NODES = override
        return sched, sched.KERNEL_MIN_NODES

    def test_scalar_and_kernel_paths_agree_exactly(
        self, name, override, module, entry, delta
    ):
        sched, boundary = self._make(name, override)
        n_nodes = boundary + delta
        items = boundary_items()

        def decide(s):
            cluster = boundary_cluster(n_nodes)
            return [s.place(it, cluster) for it in items]

        scalar = create_scheduler(name)
        scalar.use_kernel = False
        kernel = create_scheduler(name)
        kernel.KERNEL_MIN_NODES = 0
        auto = decide(sched)
        for label, other in (("scalar", decide(scalar)), ("kernel", decide(kernel))):
            for da, db in zip(auto, other):
                assert da.placement == db.placement, (
                    f"{name} auto vs {label} at {n_nodes} nodes"
                )
                assert da.candidates_considered == db.candidates_considered
                assert da.reason == db.reason

    def test_dispatch_flips_exactly_at_the_boundary(
        self, name, override, module, entry, delta, monkeypatch
    ):
        sched, boundary = self._make(name, override)
        n_nodes = boundary + delta
        calls = []
        orig = getattr(module, entry)
        monkeypatch.setattr(
            module, entry, lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
        )
        cluster = boundary_cluster(n_nodes)
        sched.place(boundary_items(1)[0], cluster)  # single item: no batch rule
        used_kernel = bool(calls)
        assert used_kernel == (n_nodes >= boundary), (
            f"{name}: kernel dispatch at {n_nodes} nodes with boundary {boundary}"
        )
