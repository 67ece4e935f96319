"""Golden-equivalence tests for the jitted D-Rex SC kernel.

The scalar numpy path (``DRexSC.place_scalar``) is the reference oracle;
the jax kernel (``repro.core.sc_kernel``) and the batched
``PlacementEngine.place_many`` scoring built on it must reproduce its
decisions bit-for-bit.  Styled after ``TestLegacyEquivalence``: the
``GOLDEN`` placements below were captured from the scalar oracle at the
commit introducing the kernel, so *both* paths are pinned against drift.
"""

import numpy as np
import pytest

from repro.core import (
    ClusterView,
    DataItem,
    DRexSC,
    Placement,
    PlacementEngine,
    create_scheduler,
    get_spec,
)
from repro.storage import make_node_set, make_trace



def forced_kernel_scheduler() -> DRexSC:
    """A DRexSC that uses the kernel at any cluster size (no numpy-
    dispatch crossover), so small test clusters exercise the jit path."""
    sched = create_scheduler("drex_sc")
    sched.KERNEL_MIN_NODES = 0
    return sched


def scalar_scheduler() -> DRexSC:
    sched = create_scheduler("drex_sc")
    sched.use_kernel = False
    return sched


class TestGoldenPlacements:
    """Pinned traces -> pinned placements, for both implementations."""

    # (nodeset, trace seed) -> (k, p, node_ids) of the first 8 meva items
    # at RT 0.99, committed sequentially.  Captured from the scalar
    # oracle; guards oracle and kernel against silent drift.
    GOLDEN = {
        ("most_used", 3): [
            (3, 1, (3, 9, 0, 2)),
            (3, 1, (1, 4, 5, 6)),
            (4, 1, (8, 0, 2, 1, 4)),
            (4, 1, (5, 1, 4, 7, 6)),
            (4, 1, (3, 9, 8, 0, 2)),
            (4, 1, (3, 9, 8, 0, 2)),
            (4, 1, (3, 9, 8, 0, 2)),
            (4, 1, (3, 9, 8, 0, 2)),
        ],
        ("most_unreliable", 11): [
            (3, 2, (1, 0, 2, 3, 4)),
            (3, 2, (1, 0, 2, 3, 4)),
            (3, 1, (7, 5, 6, 8)),
            (3, 1, (3, 4, 7, 9)),
            (3, 1, (3, 4, 7, 9)),
            (3, 1, (3, 4, 7, 9)),
            (3, 2, (1, 0, 2, 3, 4)),
            (3, 2, (1, 0, 2, 3, 4)),
        ],
    }

    def _run(self, nodeset, seed, scheduler):
        items = make_trace("meva", seed=seed, n_items=8, reliability=0.99)
        eng = PlacementEngine(make_node_set(nodeset, 0.001), scheduler)
        return [eng.place(it).placement for it in items]

    @pytest.mark.parametrize("key", sorted(GOLDEN))
    def test_scalar_oracle_matches_golden(self, key):
        got = self._run(*key, scalar_scheduler())
        want = [Placement(k, p, ids) for k, p, ids in self.GOLDEN[key]]
        assert got == want

    @pytest.mark.parametrize("key", sorted(GOLDEN))
    def test_kernel_matches_golden(self, key):
        got = self._run(*key, forced_kernel_scheduler())
        want = [Placement(k, p, ids) for k, p, ids in self.GOLDEN[key]]
        assert got == want

    @pytest.mark.parametrize("key", sorted(GOLDEN))
    def test_batched_place_many_matches_golden(self, key):
        nodeset, seed = key
        items = make_trace("meva", seed=seed, n_items=8, reliability=0.99)
        eng = PlacementEngine(make_node_set(nodeset, 0.001), forced_kernel_scheduler())
        got = [r.placement for r in eng.place_many(items)]
        want = [Placement(k, p, ids) for k, p, ids in self.GOLDEN[key]]
        assert got == want


class TestKernelOracleEquivalence:
    """Kernel decisions == scalar oracle decisions, bit for bit."""

    @pytest.mark.parametrize("nodeset", ["most_used", "most_unreliable", "most_reliable"])
    @pytest.mark.parametrize("rt", [0.9, 0.99999, "random_nines"])
    def test_sequential_place_matches_oracle(self, nodeset, rt):
        items = make_trace("meva", seed=7, n_items=40, reliability=rt)
        a = PlacementEngine(make_node_set(nodeset, 0.001), scalar_scheduler())
        b = PlacementEngine(make_node_set(nodeset, 0.001), forced_kernel_scheduler())
        for it in items:
            ra, rb = a.place(it), b.place(it)
            assert ra.placement == rb.placement
            assert ra.candidates_considered == rb.candidates_considered
            assert ra.reason == rb.reason
        np.testing.assert_array_equal(a.cluster.used_mb, b.cluster.used_mb)

    def test_batched_place_many_matches_sequential_oracle(self):
        items = make_trace("sentinel2", seed=5, n_items=60, reliability=0.95)
        a = PlacementEngine(make_node_set("most_used", 0.001), scalar_scheduler())
        pa = [a.place(it).placement for it in items]
        b = PlacementEngine(make_node_set("most_used", 0.001), forced_kernel_scheduler())
        pb = [r.placement for r in b.place_many(items)]
        assert pa == pb
        np.testing.assert_array_equal(a.cluster.used_mb, b.cluster.used_mb)
        assert a.scheduler.smin_mb == b.scheduler.smin_mb

    def test_non_committing_batch_single_call_matches_oracle(self):
        # auto_commit=False: nothing invalidates, the whole queue is
        # scored against one snapshot (the Table-2 decision-cost protocol).
        items = make_trace("meva", seed=9, n_items=50, reliability=0.99)
        a = PlacementEngine(
            make_node_set("most_used", 0.001), scalar_scheduler(), auto_commit=False
        )
        pa = [a.place(it).placement for it in items]
        b = PlacementEngine(
            make_node_set("most_used", 0.001),
            forced_kernel_scheduler(),
            auto_commit=False,
        )
        pb = [r.placement for r in b.place_many(items)]
        assert pa == pb

    def test_matches_oracle_with_dead_nodes(self):
        items = make_trace("meva", seed=13, n_items=30, reliability=0.9)
        a = PlacementEngine(make_node_set("most_used", 0.001), scalar_scheduler())
        b = PlacementEngine(make_node_set("most_used", 0.001), forced_kernel_scheduler())
        for eng in (a, b):
            eng.cluster.fail_node(0)
            eng.cluster.fail_node(4)
        pa = [a.place(it).placement for it in items]
        pb = [b.place(it).placement for it in items]
        assert pa == pb

    def test_matches_oracle_on_larger_cluster(self):
        # Exercises the budget cap (L*(L-1)/2 > MAX_MAPPINGS) and the
        # start-major enumeration order at a non-trivial scale.
        rng = np.random.default_rng(2)
        from repro.core import StorageNode

        nodes = [
            StorageNode(
                node_id=i,
                capacity_mb=float(rng.uniform(5e4, 2e5)),
                write_bw=float(rng.uniform(100, 250)),
                read_bw=float(rng.uniform(100, 400)),
                annual_failure_rate=float(rng.uniform(0.003, 0.08)),
            )
            for i in range(60)
        ]
        items = [
            DataItem(i, float(rng.uniform(10, 500)), float(i), 365.0, 0.999)
            for i in range(20)
        ]
        a = PlacementEngine(ClusterView.from_nodes(nodes), scalar_scheduler())
        b = PlacementEngine(ClusterView.from_nodes(nodes), forced_kernel_scheduler())
        pa = [a.place(it).placement for it in items]
        pb = [r.placement for r in b.place_many(items)]
        assert pa == pb

    def test_rejections_match_oracle(self):
        from repro.core import StorageNode

        # Nodes that essentially always fail within the window make any
        # meaningful target infeasible; a 1e12 MB item exhausts capacity.
        doomed = [
            StorageNode(i, 1e6, 200.0, 250.0, annual_failure_rate=500.0)
            for i in range(6)
        ]
        eng_a = PlacementEngine(ClusterView.from_nodes(doomed), scalar_scheduler())
        eng_b = PlacementEngine(
            ClusterView.from_nodes(doomed), forced_kernel_scheduler()
        )
        huge = DataItem(0, 1e12, 0.0, 365.0, 0.9)
        impossible = DataItem(1, 10.0, 0.0, 365.0, 0.999999)
        for it in (huge, impossible):
            ra, rb = eng_a.place(it), eng_b.place(it)
            assert ra.placement is None and rb.placement is None
            assert ra.reason == rb.reason

    def test_fewer_than_two_live_nodes(self):
        nodes = make_node_set("most_used", 0.001)[:2]
        eng = PlacementEngine(ClusterView.from_nodes(nodes), forced_kernel_scheduler())
        eng.cluster.fail_node(0)
        rec = eng.place(DataItem(0, 1.0, 0.0, 365.0, 0.9))
        assert rec.placement is None
        assert "fewer than 2" in rec.reason

    def test_registry_declares_batch_scoring_capability(self):
        assert get_spec("drex_sc").capabilities.batch_scoring
        # every hot-path adaptive scheduler is on the batched kernel
        # path as of the LB kernel (tests/test_lb_vectorized.py)
        assert get_spec("drex_lb").capabilities.batch_scoring

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

    def test_place_batch_running_smin_matches_sequential_observation(self):
        # Item j in a batch must be scored with the smallest size among
        # items 0..j (plus history), exactly as sequential place observes.
        sched_batch = forced_kernel_scheduler()
        sched_seq = scalar_scheduler()
        cluster = ClusterView.from_nodes(make_node_set("most_used", 0.001))
        # A shrinking size sequence moves the smin anchor mid-batch.
        items = [
            DataItem(i, size, float(i), 365.0, 0.95)
            for i, size in enumerate([500.0, 300.0, 80.0, 2.0, 60.0, 400.0])
        ]
        got = [d.placement for d in sched_batch.place_batch(items, cluster)]
        want = [sched_seq.place(it, cluster).placement for it in items]
        assert got == want


class TestDistinctRows:
    """A launch scores each distinct row of kernel inputs once and fans
    the winners back to every item, with decisions unchanged."""

    #: a checkpoint save's four bucket sizes (4 KiB to 32 MiB) x 64, on
    #: nodes small enough that size moves the decision and 2 GB fits none
    SIZES = (0.25, 64.0, 512.0, 2048.0)

    @pytest.fixture
    def launched(self, monkeypatch):
        from repro.core import sc_kernel

        calls = []
        score = sc_kernel.score_windows_batch

        def spy(*args):
            calls.append(args)
            return score(*args)

        monkeypatch.setattr(sc_kernel, "score_windows_batch", spy)
        return calls

    def _repeated(self):
        """64 items over the four sizes, smallest first (one smin anchor
        for every row), the rest in shuffled order."""
        sizes = list(self.SIZES * 16)
        rest = np.random.default_rng(0).permutation(sizes[1:]).tolist()
        return self._items(sizes[:1] + rest)

    def _items(self, sizes):
        return [DataItem(i, s, float(i), 365.0, 0.99999) for i, s in enumerate(sizes)]

    def _engines(self):
        nodes = make_node_set("most_used", 1e-5)
        return (
            PlacementEngine(nodes, scalar_scheduler(), auto_commit=False),
            PlacementEngine(nodes, forced_kernel_scheduler(), auto_commit=False),
        )

    def test_repeated_rows_match_per_row_scoring_and_oracle(self, launched):
        items = self._repeated()
        oracle, batched = self._engines()
        want = [oracle.place(it) for it in items]
        got = batched.place_many(items)
        sched = forced_kernel_scheduler()
        sched.smin_mb = min(self.SIZES)
        per_row = [sched.place_batch([it], batched.cluster)[0] for it in items]
        for w, g, r in zip(want, got, per_row):
            assert w.placement == g.placement == r.placement
            assert w.candidates_considered == g.candidates_considered == r.candidates_considered
            assert w.reason == g.reason == r.reason
        assert len({g.placement for g in got}) == 3 and not all(g.ok for g in got)

    def test_repeated_rows_launch_only_the_distinct_rows(self, launched):
        items = self._repeated()
        self._engines()[1].place_many(items)
        assert launched
        for args in launched:
            rows = np.column_stack(args[:6])
            assert len(rows) == len({r.tobytes() for r in rows}) == len(self.SIZES)
        assert sum(len(args[0]) for args in launched) == len(self.SIZES) * len(launched)

    def test_all_distinct_rows_are_scored_as_before(self, launched):
        items = self._items([1.0 + i for i in range(24)])
        sched = forced_kernel_scheduler()
        cluster = ClusterView.from_nodes(make_node_set("most_used", 0.001))
        got = sched.place_batch(items, cluster)
        (args,) = launched
        smins = [1.0] * len(items)
        _, _, want_args = sched._kernel_inputs(items, smins, cluster, None)
        assert len(args) == len(want_args)
        for a, b in zip(args, want_args):
            if isinstance(b, np.ndarray):
                assert a.tobytes() == b.tobytes() and a.shape == b.shape
            else:
                assert a == b
        oracle = scalar_scheduler()
        assert [d.placement for d in got] == [oracle.place(it, cluster).placement for it in items]

    def test_rows_differing_only_in_smin_stay_apart(self, launched):
        # sizes 8, 8, 1, 8: the running smin anchor moves at the third
        # item, so the fourth row repeats the first's size under a new smin
        items = self._items([8.0, 8.0, 1.0, 8.0])
        sched = forced_kernel_scheduler()
        cluster = ClusterView.from_nodes(make_node_set("most_used", 0.001))
        got = sched.place_batch(items, cluster)
        (args,) = launched
        np.testing.assert_array_equal(args[1], [8.0, 1.0, 8.0])
        np.testing.assert_array_equal(args[3], [8.0, 1.0, 1.0])
        oracle = scalar_scheduler()
        assert [d.placement for d in got] == [oracle.place(it, cluster).placement for it in items]

    @pytest.mark.parametrize(
        "rows,first,inverse",
        [
            ([[0.0, 1.0], [-0.0, 1.0], [0.0, 1.0]], [0, 1], [0, 1, 0]),
            ([[0.5, 2.0, 1.0], [0.5, 2.0, 0.25], [0.5, 2.0, 1.0]], [0, 1], [0, 1, 0]),
            ([[1.0], [2.0], [3.0]], [0, 1, 2], [0, 1, 2]),
        ],
        ids=["sign_of_zero", "smin_only", "all_distinct"],
    )
    def test_distinct_rows_key_by_bytes(self, rows, first, inverse):
        from repro.core.algorithms import _distinct_rows

        rows = np.asarray(rows, dtype=np.float64)
        got_first, got_inverse = _distinct_rows(rows)
        assert got_first.tolist() == first
        assert got_inverse.tolist() == inverse
        np.testing.assert_array_equal(rows[got_first][got_inverse], rows)
