"""Golden-equivalence tests for the jitted D-Rex LB kernel.

The scalar numpy path (``DRexLB.place_scalar``) is the reference oracle;
the jax kernel (``repro.core.lb_kernel``) and the batched
``PlacementEngine.place_many`` scoring built on it must reproduce its
decisions bit-for-bit.  Styled after tests/test_greedy_vectorized.py:
the ``GOLDEN`` placements below were captured from the scalar oracle at
the commit introducing the kernel, so *both* paths are pinned against
drift.  Coverage spans:

* normal heterogeneous clusters (the balance penalty discriminating
  between many feasible K at P = 1),
* capacity-tight clusters (the per-column capacity range collapsing),
* low-reliability regimes (high parity demand — the host frontier rows
  are exact at every width, so there is no fallback regime to hide in),
* the summation-order policy: penalties accumulate in prefix-sum order
  on both paths and the parity frontier enters the kernel as a host
  input (see the lb_kernel module docstring), so decisions are equal
  bit-for-bit, not approximately.
"""

import numpy as np
import pytest

from repro.core import (
    ClusterView,
    DataItem,
    Placement,
    PlacementEngine,
    StorageNode,
    create_scheduler,
    get_spec,
)
from repro.storage import make_node_set, make_trace



def forced_kernel_scheduler():
    """A DRexLB that uses the kernel at any cluster size (no numpy-
    dispatch crossover), so small test clusters hit the jit path."""
    sched = create_scheduler("drex_lb")
    sched.KERNEL_MIN_NODES = 0
    sched.KERNEL_MIN_NODES_BATCH = 0
    return sched


def scalar_scheduler():
    sched = create_scheduler("drex_lb")
    sched.use_kernel = False
    return sched


def random_cluster(
    seed: int, n: int, *, tight: bool = False, afr_hi: float = 0.2
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


# (nodeset, trace seed) -> (k, p, node_ids) of the first 8 meva items at
# RT 0.99, committed sequentially.  Captured from the scalar oracle;
# guards oracle and kernel against silent drift.  The homogeneous set is
# the discriminating one: every node has identical free space, so the
# balance penalty (not first-feasibility) picks the wide K=9 mapping.
GOLDEN = {
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
    ("homogeneous", 5): [
        (9, 1, (0, 1, 2, 3, 4, 5, 6, 7, 8, 9)),
    ] * 8,
}

GOLDEN_KEYS = sorted(GOLDEN)


class TestGoldenPlacements:
    """Pinned traces -> pinned placements, for both implementations."""

    def _run(self, nodeset, seed, scheduler):
        items = make_trace("meva", seed=seed, n_items=8, reliability=0.99)
        eng = PlacementEngine(make_node_set(nodeset, 0.001), scheduler)
        return [eng.place(it).placement for it in items]

    @pytest.mark.parametrize("key", GOLDEN_KEYS)
    def test_scalar_oracle_matches_golden(self, key):
        got = self._run(*key, scalar_scheduler())
        want = [Placement(k, p, ids) for k, p, ids in GOLDEN[key]]
        assert got == want

    @pytest.mark.parametrize("key", GOLDEN_KEYS)
    def test_kernel_matches_golden(self, key):
        got = self._run(*key, forced_kernel_scheduler())
        want = [Placement(k, p, ids) for k, p, ids in GOLDEN[key]]
        assert got == want

    @pytest.mark.parametrize("key", GOLDEN_KEYS)
    def test_batched_place_many_matches_golden(self, key):
        nodeset, seed = key
        items = make_trace("meva", seed=seed, n_items=8, reliability=0.99)
        eng = PlacementEngine(
            make_node_set(nodeset, 0.001), forced_kernel_scheduler()
        )
        got = [r.placement for r in eng.place_many(items)]
        want = [Placement(k, p, ids) for k, p, ids in GOLDEN[key]]
        assert got == want


class TestKernelOracleEquivalence:
    """Kernel decisions == scalar oracle decisions, bit for bit."""

    def _assert_sequential_equal(self, cluster, items, ctx=None):
        a = scalar_scheduler()
        b = forced_kernel_scheduler()
        for it in items:
            da = a.place(it, cluster)
            db = b.place(it, cluster, ctx=ctx)
            assert da.placement == db.placement, f"item {it.item_id}"
            assert da.candidates_considered == db.candidates_considered
            assert da.reason == db.reason

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("n", [5, 10, 40, 65, 120])
    def test_random_clusters(self, seed, n):
        self._assert_sequential_equal(
            random_cluster(seed * 100 + n, n), random_items(seed)
        )

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_capacity_tight_clusters(self, seed):
        # Tight free space engages the per-column capacity range: most
        # columns' largest feasible K no longer fits the chunk.
        self._assert_sequential_equal(
            random_cluster(seed, 40, tight=True),
            random_items(seed, size_hi=900.0),
        )

    @pytest.mark.parametrize("seed", [0, 1])
    def test_low_reliability_regime(self, seed):
        # High AFRs + many-nines targets: large minimum parities, deep
        # feasibility scans, frequent rejections.
        self._assert_sequential_equal(
            random_cluster(seed + 50, 30, afr_hi=3.0),
            [
                DataItem(i, 10.0 + i, float(i), 365.0, rt)
                for i, rt in enumerate([0.9, 0.999, 0.9999999, 0.99])
            ],
        )

    def test_extreme_parity_demand_matches_scalar(self):
        # Atrocious nodes: the smallest feasible parity lands above 100.
        # The host frontier rows are exact at every width, so the kernel
        # resolves even this regime in-grid (no fallback path exists).
        cluster = ClusterView.from_nodes(
            [
                StorageNode(i, 1e6, 200.0, 250.0, annual_failure_rate=3.5)
                for i in range(160)
            ]
        )
        item = DataItem(0, 10.0, 0.0, 365.0, 0.9)
        want = scalar_scheduler().place(item, cluster)
        got = forced_kernel_scheduler().place(item, cluster)
        assert want.placement is not None
        assert want.placement.p > 100  # the regime is real
        assert got.placement == want.placement
        assert got.candidates_considered == want.candidates_considered

    def test_batched_place_many_matches_sequential_oracle(self):
        items = make_trace("sentinel2", seed=5, n_items=40, reliability=0.95)
        a = PlacementEngine(make_node_set("most_used", 0.001), scalar_scheduler())
        pa = [a.place(it).placement for it in items]
        b = PlacementEngine(
            make_node_set("most_used", 0.001), forced_kernel_scheduler()
        )
        pb = [r.placement for r in b.place_many(items)]
        assert pa == pb
        np.testing.assert_array_equal(a.cluster.used_mb, b.cluster.used_mb)

    def test_non_committing_batch_matches_oracle(self):
        # auto_commit=False: nothing invalidates, the whole queue is
        # scored against one snapshot (the Table-2 decision-cost protocol).
        items = make_trace("meva", seed=9, n_items=30, reliability=0.99)
        a = PlacementEngine(
            make_node_set("most_used", 0.001), scalar_scheduler(),
            auto_commit=False,
        )
        pa = [a.place(it).placement for it in items]
        b = PlacementEngine(
            make_node_set("most_used", 0.001), forced_kernel_scheduler(),
            auto_commit=False,
        )
        pb = [r.placement for r in b.place_many(items)]
        assert pa == pb

    def test_matches_oracle_with_dead_nodes(self):
        items = make_trace("meva", seed=13, n_items=20, reliability=0.9)
        cluster = ClusterView.from_nodes(make_node_set("most_used", 0.001))
        cluster.fail_node(0)
        cluster.fail_node(4)
        self._assert_sequential_equal(cluster, items)

    def test_rejections_match_oracle(self):
        doomed = ClusterView.from_nodes(
            [StorageNode(i, 1e6, 200.0, 250.0, annual_failure_rate=500.0)
             for i in range(6)]
        )
        a = scalar_scheduler()
        b = forced_kernel_scheduler()
        for it in (
            DataItem(0, 1e12, 0.0, 365.0, 0.9),
            DataItem(1, 10.0, 0.0, 365.0, 0.999999),
        ):
            da, db = a.place(it, doomed), b.place(it, doomed)
            assert da.placement is None and db.placement is None
            assert da.reason == db.reason
            assert da.candidates_considered == db.candidates_considered

    def test_fewer_than_three_live_nodes(self):
        cluster = ClusterView.from_nodes(make_node_set("most_used", 0.001)[:3])
        cluster.fail_node(0)
        rec = forced_kernel_scheduler().place(
            DataItem(0, 1.0, 0.0, 365.0, 0.9), cluster
        )
        assert rec.placement is None
        assert "fewer than 3" in rec.reason

    def test_registry_declares_batch_scoring_capability(self):
        assert get_spec("drex_lb").capabilities.batch_scoring
        # f_avg makes every LB score cluster-global: it must never claim
        # window-local scores (see the capability's docstring).
        assert not get_spec("drex_lb").capabilities.windowed_scoring

    def test_place_batch_is_pure(self):
        # Scoring a batch must not mutate scheduler state or the cluster.
        sched = forced_kernel_scheduler()
        cluster = ClusterView.from_nodes(make_node_set("most_used", 0.001))
        items = make_trace("meva", seed=1, n_items=10, reliability=0.9)
        used0 = cluster.used_mb.copy()
        smin0 = sched.smin_mb
        sched.place_batch(items, cluster)
        np.testing.assert_array_equal(cluster.used_mb, used0)
        assert sched.smin_mb == smin0


class TestSummationOrderPolicy:
    """The penalty prefix sums are sequential on both paths: ulp-level
    agreement, not just same-argmin agreement."""

    def test_scalar_penalty_is_plain_cumsum(self):
        # The oracle's documented order: np.cumsum of the chunk-adjusted
        # deviations.  Recompute one decision's penalty by hand.
        cluster = random_cluster(3, 25)
        item = DataItem(0, 80.0, 0.0, 365.0, 0.99)
        rec = scalar_scheduler().place(item, cluster)
        pl = rec.placement
        assert pl is not None
        ids = cluster.live_ids()
        order = ids[np.argsort(-cluster.free_mb[ids], kind="stable")]
        free_sorted = cluster.free_mb[order]
        f_avg = float(free_sorted.mean())
        chunk = item.size_mb / float(pl.k)
        pen = np.cumsum(np.abs(free_sorted - chunk - f_avg))
        dev = np.abs(free_sorted - f_avg)
        suffix = np.concatenate([np.cumsum(dev[::-1])[::-1], [0.0]])
        want_bp = pen[pl.n - 1] + suffix[pl.n]
        # Any competing K at the same P must have a strictly larger
        # penalty (or equal with a larger K) under the same order.
        for k in range(2, len(order) - pl.p + 1):
            if k == pl.k:
                continue
            n = k + pl.p
            ck = item.size_mb / float(k)
            bp = np.cumsum(np.abs(free_sorted - ck - f_avg))[n - 1] + suffix[n]
            feasible = cluster.free_mb[order[:n]].min() >= ck
            if feasible and bp < want_bp:
                raise AssertionError("oracle did not pick the min-penalty K")

    def test_kernel_bitwise_equal_on_wide_mappings(self):
        # Mappings much wider than numpy's pairwise-sum block (8) — the
        # regime where an unfixed summation order would diverge in ulps.
        # Near-homogeneous free space makes the balance penalty favor
        # spreading wide (cf. the homogeneous golden).
        rng = np.random.default_rng(17)
        cluster = ClusterView.from_nodes(
            [
                StorageNode(
                    i, 5e4, float(rng.uniform(50, 400)),
                    float(rng.uniform(50, 450)), 0.02,
                    used_mb=float(rng.uniform(0.0, 10.0)),
                )
                for i in range(80)
            ]
        )
        items = [DataItem(i, 300.0 + i, float(i), 365.0, 0.9) for i in range(6)]
        a, b = scalar_scheduler(), forced_kernel_scheduler()
        for it in items:
            da, db = a.place(it, cluster), b.place(it, cluster)
            assert da.placement == db.placement
            assert da.placement is not None and da.placement.n > 8
