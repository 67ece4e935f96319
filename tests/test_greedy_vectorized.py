"""Golden-equivalence tests for the jitted greedy-scheduler kernels.

The scalar numpy paths (``GreedyMinStorage.place_scalar`` /
``GreedyLeastUsed.place_scalar``) are the reference oracles; the jax
kernels (``repro.core.greedy_kernel``) and the batched
``PlacementEngine.place_many`` scoring built on them must reproduce
their decisions bit-for-bit.  Styled after tests/test_sc_vectorized.py:
the ``GOLDEN`` placements below were captured from the scalar oracles at
the commit introducing the kernels, so *both* paths are pinned against
drift.  Coverage deliberately spans the kernels' three regimes:

* exact-DP feasibility (mappings of <= ``_AUTO_EXACT_LIMIT`` nodes),
* the RNA approximation regime (larger clusters, host-computed frontier
  rows via :func:`reliability.rna_parity_frontier`),
* the hybrid fallbacks (GreedyMinStorage's capacity-tight ``slow`` rows,
  GreedyLeastUsed's beyond-``SCAN_CAP`` first-feasible N).
"""

import numpy as np
import pytest

from repro.core import (
    BatchContext,
    ClusterView,
    DataItem,
    Placement,
    PlacementEngine,
    StorageNode,
    create_scheduler,
    get_spec,
)
from repro.core import greedy_kernel
from repro.core.reliability import (
    _AUTO_EXACT_LIMIT,
    min_parity_for_target,
    rna_parity_frontier,
)
from repro.storage import make_node_set, make_trace


GREEDY = ("greedy_min_storage", "greedy_least_used")


def forced_kernel_scheduler(name: str):
    """A greedy scheduler that uses the kernel at any cluster size (no
    numpy-dispatch crossover), so small test clusters hit the jit path."""
    sched = create_scheduler(name)
    sched.KERNEL_MIN_NODES = 0
    sched.KERNEL_MIN_NODES_BATCH = 0
    return sched


def scalar_scheduler(name: str):
    sched = create_scheduler(name)
    sched.use_kernel = False
    return sched


def random_cluster(
    seed: int,
    n: int,
    *,
    tight: bool = False,
    afr_hi: float = 0.2,
) -> ClusterView:
    rng = np.random.default_rng(seed)
    cap_lo, cap_hi, used_hi = (
        (50.0, 800.0, 300.0) if tight else (2e3, 1e5, 1e3)
    )
    nodes = [
        StorageNode(
            node_id=i,
            capacity_mb=float(rng.uniform(cap_lo, cap_hi)),
            write_bw=float(rng.uniform(50, 400)),
            read_bw=float(rng.uniform(50, 450)),
            annual_failure_rate=float(rng.uniform(0.001, afr_hi)),
            used_mb=float(rng.uniform(0.0, used_hi)),
        )
        for i in range(n)
    ]
    return ClusterView.from_nodes(nodes)


def random_items(seed: int, count: int = 6, size_hi: float = 500.0):
    rng = np.random.default_rng(seed + 1)
    targets = [0.9, 0.99, 0.999, 0.99999]
    return [
        DataItem(
            item_id=i,
            size_mb=float(rng.uniform(1.0, size_hi)),
            arrival_time=float(i),
            delta_t_days=float(rng.uniform(30.0, 730.0)),
            reliability_target=targets[int(rng.integers(len(targets)))],
        )
        for i in range(count)
    ]


# scheduler -> (nodeset, trace seed) -> (k, p, node_ids) of the first
# 8 meva items at RT 0.99, committed sequentially.  Captured from the
# scalar oracles; guards oracle and kernel against silent drift.
GOLDEN = {
        "greedy_min_storage": {
            ("most_used", 3): [
                (9, 1, (9, 3, 0, 2, 8, 1, 4, 5, 6, 7)),
            ] * 8,
            ("most_unreliable", 11): [
                (5, 2, (1, 0, 2, 3, 4, 7, 9)),
            ] * 8,
        },
        "greedy_least_used": {
            ("most_used", 3): [
                (2, 1, (3, 9, 0)),
                (2, 1, (3, 9, 2)),
                (2, 1, (3, 9, 8)),
                (2, 1, (3, 9, 2)),
                (2, 1, (3, 9, 2)),
                (2, 1, (3, 9, 8)),
                (2, 1, (3, 9, 2)),
                (2, 1, (3, 9, 2)),
            ],
            ("most_unreliable", 11): [
                (2, 2, (1, 0, 2, 3)),
                (2, 2, (1, 0, 2, 4)),
                (2, 2, (1, 0, 2, 3)),
                (2, 2, (1, 0, 2, 4)),
                (2, 2, (1, 0, 2, 4)),
                (2, 2, (1, 0, 2, 3)),
                (2, 2, (1, 0, 2, 4)),
                (2, 2, (1, 0, 2, 3)),
        ],
    },
}

GOLDEN_KEYS = [(name, key) for name in GREEDY for key in sorted(GOLDEN[name])]


class TestGoldenPlacements:
    """Pinned traces -> pinned placements, for both implementations."""

    def _run(self, nodeset, seed, scheduler):
        items = make_trace("meva", seed=seed, n_items=8, reliability=0.99)
        eng = PlacementEngine(make_node_set(nodeset, 0.001), scheduler)
        return [eng.place(it).placement for it in items]

    @pytest.mark.parametrize("name,key", GOLDEN_KEYS)
    def test_scalar_oracle_matches_golden(self, name, key):
        got = self._run(*key, scalar_scheduler(name))
        want = [Placement(k, p, ids) for k, p, ids in GOLDEN[name][key]]
        assert got == want

    @pytest.mark.parametrize("name,key", GOLDEN_KEYS)
    def test_kernel_matches_golden(self, name, key):
        got = self._run(*key, forced_kernel_scheduler(name))
        want = [Placement(k, p, ids) for k, p, ids in GOLDEN[name][key]]
        assert got == want

    @pytest.mark.parametrize("name,key", GOLDEN_KEYS)
    def test_batched_place_many_matches_golden(self, name, key):
        nodeset, seed = key
        items = make_trace("meva", seed=seed, n_items=8, reliability=0.99)
        eng = PlacementEngine(
            make_node_set(nodeset, 0.001), forced_kernel_scheduler(name)
        )
        got = [r.placement for r in eng.place_many(items)]
        want = [Placement(k, p, ids) for k, p, ids in GOLDEN[name][key]]
        assert got == want


@pytest.mark.parametrize("name", GREEDY)
class TestKernelOracleEquivalence:
    """Kernel decisions == scalar oracle decisions, bit for bit."""

    def _assert_sequential_equal(self, name, cluster, items, ctx=None):
        a = create_scheduler(name)
        a.use_kernel = False
        b = forced_kernel_scheduler(name)
        for it in items:
            da = a.place(it, cluster)
            db = b.place(it, cluster, ctx=ctx)
            assert da.placement == db.placement, f"{name}: {it.item_id}"
            assert da.candidates_considered == db.candidates_considered
            assert da.reason == db.reason

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("n", [5, 10, 40])
    def test_exact_dp_regime(self, name, seed, n):
        self._assert_sequential_equal(
            name, random_cluster(seed * 100 + n, n), random_items(seed)
        )

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("n", [65, 80, 120])
    def test_rna_regime(self, name, seed, n):
        # Mappings larger than _AUTO_EXACT_LIMIT take the oracle's RNA
        # branch; the kernel must reproduce it via the host frontier row.
        assert n > _AUTO_EXACT_LIMIT
        self._assert_sequential_equal(
            name, random_cluster(seed * 100 + n, n), random_items(seed)
        )

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_capacity_tight_clusters(self, name, seed):
        # Tight free space engages GreedyMinStorage's capacity filter
        # (the kernel's host-finished ``slow`` rows) and GreedyLeastUsed's
        # capacity skips.
        self._assert_sequential_equal(
            name,
            random_cluster(seed, 40, tight=True),
            random_items(seed, size_hi=900.0),
        )

    def test_batched_place_many_matches_sequential_oracle(self, name):
        items = make_trace("sentinel2", seed=5, n_items=40, reliability=0.95)
        a = PlacementEngine(make_node_set("most_used", 0.001), scalar_scheduler(name))
        pa = [a.place(it).placement for it in items]
        b = PlacementEngine(
            make_node_set("most_used", 0.001), forced_kernel_scheduler(name)
        )
        pb = [r.placement for r in b.place_many(items)]
        assert pa == pb
        np.testing.assert_array_equal(a.cluster.used_mb, b.cluster.used_mb)

    def test_non_committing_batch_matches_oracle(self, name):
        # auto_commit=False: nothing invalidates, the whole queue is
        # scored against one snapshot (the Table-2 decision-cost protocol).
        items = make_trace("meva", seed=9, n_items=30, reliability=0.99)
        a = PlacementEngine(
            make_node_set("most_used", 0.001), scalar_scheduler(name),
            auto_commit=False,
        )
        pa = [a.place(it).placement for it in items]
        b = PlacementEngine(
            make_node_set("most_used", 0.001), forced_kernel_scheduler(name),
            auto_commit=False,
        )
        pb = [r.placement for r in b.place_many(items)]
        assert pa == pb

    def test_matches_oracle_with_dead_nodes(self, name):
        items = make_trace("meva", seed=13, n_items=20, reliability=0.9)
        cluster = ClusterView.from_nodes(make_node_set("most_used", 0.001))
        cluster.fail_node(0)
        cluster.fail_node(4)
        self._assert_sequential_equal(name, cluster, items)

    def test_rejections_match_oracle(self, name):
        # Nodes that essentially always fail within the window make any
        # meaningful target infeasible; a 1e12 MB item exhausts capacity.
        doomed = ClusterView.from_nodes(
            [StorageNode(i, 1e6, 200.0, 250.0, annual_failure_rate=500.0)
             for i in range(6)]
        )
        a = scalar_scheduler(name)
        b = forced_kernel_scheduler(name)
        for it in (
            DataItem(0, 1e12, 0.0, 365.0, 0.9),
            DataItem(1, 10.0, 0.0, 365.0, 0.999999),
        ):
            da, db = a.place(it, doomed), b.place(it, doomed)
            assert da.placement is None and db.placement is None
            assert da.reason == db.reason
            assert da.candidates_considered == db.candidates_considered

    def test_fewer_than_two_live_nodes(self, name):
        cluster = ClusterView.from_nodes(make_node_set("most_used", 0.001)[:2])
        cluster.fail_node(0)
        rec = forced_kernel_scheduler(name).place(
            DataItem(0, 1.0, 0.0, 365.0, 0.9), cluster
        )
        assert rec.placement is None
        assert "fewer than 2" in rec.reason

    def test_registry_declares_batch_scoring_capability(self, name):
        assert get_spec(name).capabilities.batch_scoring

    def test_place_batch_is_pure(self, name):
        # Scoring a batch must not mutate scheduler state or the cluster.
        sched = forced_kernel_scheduler(name)
        cluster = ClusterView.from_nodes(make_node_set("most_used", 0.001))
        items = make_trace("meva", seed=1, n_items=10, reliability=0.9)
        used0 = cluster.used_mb.copy()
        smin0 = sched.smin_mb
        sched.place_batch(items, cluster)
        np.testing.assert_array_equal(cluster.used_mb, used0)
        assert sched.smin_mb == smin0


class TestHybridFallbacks:
    """The kernels' host-side completion paths, exercised explicitly."""

    def test_min_storage_slow_rows_trigger_and_match(self):
        # Tight capacity: the bw-sorted prefix does not fit the chunk, so
        # the kernel must flag rows slow and finish them on the host.
        cluster = random_cluster(7, 40, tight=True)
        items = random_items(7, count=8, size_hi=900.0)
        sched = forced_kernel_scheduler("greedy_min_storage")
        orig = greedy_kernel.min_storage_batch
        slow_rows = 0

        def spy(*args, **kwargs):
            nonlocal slow_rows
            out = orig(*args, **kwargs)
            slow_rows += int(out[1].sum())
            return out

        greedy_kernel.min_storage_batch = spy
        try:
            got = [sched.place(it, cluster).placement for it in items]
        finally:
            greedy_kernel.min_storage_batch = orig
        assert slow_rows > 0, "expected the capacity filter to engage"
        oracle = scalar_scheduler("greedy_min_storage")
        want = [oracle.place(it, cluster).placement for it in items]
        assert got == want

    def test_least_used_scan_cap_fallback(self):
        # Very unreliable nodes + a many-nines target push the first
        # feasible N beyond SCAN_CAP; the kernel falls back to the scalar
        # oracle for those items.
        cluster = random_cluster(0, 90, afr_hi=5.0)
        item = DataItem(0, 5.0, 0.0, 365.0, 0.9999999)
        sched = forced_kernel_scheduler("greedy_least_used")
        got = sched.place(item, cluster)
        want = scalar_scheduler("greedy_least_used").place(item, cluster)
        assert got.placement == want.placement
        assert got.candidates_considered == want.candidates_considered
        assert got.placement is not None
        assert got.placement.n > sched.SCAN_CAP

    def test_rna_frontier_row_matches_min_parity_for_target(self):
        rng = np.random.default_rng(11)
        for trial in range(8):
            L = int(rng.integers(_AUTO_EXACT_LIMIT + 1, 140))
            probs = rng.uniform(0.0, 0.6, size=L)
            if trial == 0:
                probs = np.zeros(L)  # degenerate var == 0 branch
            target = float(rng.choice([0.9, 0.999, 0.9999999]))
            row = greedy_kernel.rna_frontier_row(probs, target, L)
            assert np.all(row[: _AUTO_EXACT_LIMIT + 1] == -1)
            for n in range(_AUTO_EXACT_LIMIT + 1, L + 1):
                want = min_parity_for_target(probs[:n], target)
                assert row[n] == (-1 if want is None else want)

    def test_rna_parity_frontier_range_bounds(self):
        probs = np.full(70, 0.01)
        row = rna_parity_frontier(probs, 0.99, 65, 70)
        assert row.shape == (6,)
        for i, n in enumerate(range(65, 71)):
            want = min_parity_for_target(probs[:n], 0.99)
            assert row[i] == (-1 if want is None else want)


class TestBatchContextRnaCache:
    def test_rna_rows_are_cached_and_exact(self):
        ctx = BatchContext()
        probs = np.random.default_rng(5).uniform(0.0, 0.3, size=100)
        a = ctx.rna_frontier(probs, 0.999, 100)
        misses0 = ctx.misses
        b = ctx.rna_frontier(probs, 0.999, 100)
        assert ctx.misses == misses0 and ctx.hits >= 1
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(
            a, greedy_kernel.rna_frontier_row(probs, 0.999, 100)
        )
