"""Top-M pre-filter exactness at the dispatch boundary (core/prefilter).

The pre-filter slices kernel inputs to the freest-M prefix of the live
nodes (see :mod:`repro.core.prefilter` for the per-scheduler
losslessness arguments).  These tests pin:

* kernel-vs-oracle agreement at ``M - 1`` / ``M`` / ``M + 1`` live nodes
  for each filtered scheduler — the filter engages exactly when the live
  count exceeds the cap, so the boundary is where a slicing bug would
  first change a decision;
* free-space-key *ties* straddling the cut: ``_live_sorted`` is a
  stable sort, so the filtered prefix must be a prefix of the unfiltered
  order even when every node ties;
* the D-Rex LB fallback lane: rows whose sufficiency test fails re-run
  unfiltered and still match the scalar oracle bit-for-bit;
* telemetry accounting (``engaged == accepted + fallback``);
* a registry sweep: every ``batch_scoring`` scheduler's filtered batch
  decisions are bit-identical to its sequential scalar-oracle decisions
  on randomized clusters large enough for the filter to engage.
"""

import numpy as np
import pytest

from repro.core import (
    ClusterView,
    DataItem,
    SCHEDULER_NAMES,
    StorageNode,
    create_scheduler,
    get_spec,
    scheduler_names,
)
from repro.core import prefilter


ALL_REGISTERED = sorted(set(scheduler_names()) | set(SCHEDULER_NAMES))


def make_cluster(n: int, seed: int = 0, afr_hi: float = 0.1, ties: bool = False):
    """``ties=True`` gives every node identical free space, so *every*
    prefix boundary is a tie and only the stable sort order breaks it."""
    rng = np.random.default_rng(seed)
    return ClusterView.from_nodes(
        [
            StorageNode(
                node_id=i,
                capacity_mb=5e4 if ties else float(rng.uniform(2e3, 1e5)),
                write_bw=float(rng.uniform(50, 400)),
                read_bw=float(rng.uniform(50, 450)),
                annual_failure_rate=float(rng.uniform(0.001, afr_hi)),
                used_mb=0.0 if ties else float(rng.uniform(0.0, 1e3)),
            )
            for i in range(n)
        ]
    )


def make_items(count: int = 6, seed: int = 1, target: float | None = None):
    rng = np.random.default_rng(seed)
    targets = [0.9, 0.99, 0.999]
    return [
        DataItem(
            i,
            float(rng.uniform(1.0, 400.0)),
            float(i),
            float(rng.uniform(30.0, 730.0)),
            target
            if target is not None
            else targets[int(rng.integers(len(targets)))],
        )
        for i in range(count)
    ]


def _tuned(name: str, **overrides):
    """Scheduler with the kernel forced on and small caps so the filter
    engages on test-sized clusters; identical tuning must be applied to
    the oracle instance (caps like ``MAX_MAPPINGS`` are part of the
    algorithm, not just the filter)."""
    sched = create_scheduler(name)
    for attr, val in overrides.items():
        assert hasattr(type(sched), attr), f"{name} has no {attr}"
        setattr(sched, attr, val)
    for attr in ("KERNEL_MIN_NODES", "KERNEL_MIN_NODES_BATCH"):
        if hasattr(type(sched), attr):
            setattr(sched, attr, 0)
    return sched


def assert_decisions_match(got, want, label):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.placement == b.placement, label
        assert a.candidates_considered == b.candidates_considered, label
        assert a.reason == b.reason, label


LB_CAP = 8  # instance override; lb_batch needs m >= 3


@pytest.mark.parametrize("delta", [-1, 0, 1])
class TestLBBoundary:
    def _pair(self):
        filt = _tuned("drex_lb", PREFILTER_CAP=LB_CAP)
        oracle = _tuned("drex_lb", PREFILTER_CAP=LB_CAP)
        oracle.use_kernel = False
        return filt, oracle

    def test_matches_scalar_oracle_at_the_cut(self, delta):
        filt, oracle = self._pair()
        cluster = make_cluster(LB_CAP + delta)
        items = make_items()
        got = filt.place_batch(items, cluster)
        want = [oracle.place_scalar(it, cluster) for it in items]
        assert_decisions_match(got, want, f"drex_lb at cap{delta:+d}")

    def test_ties_at_the_cut(self, delta):
        filt, oracle = self._pair()
        cluster = make_cluster(LB_CAP + delta, ties=True)
        items = make_items()
        got = filt.place_batch(items, cluster)
        want = [oracle.place_scalar(it, cluster) for it in items]
        assert_decisions_match(got, want, f"drex_lb ties at cap{delta:+d}")

    def test_engagement_flips_exactly_at_the_cap(self, delta):
        filt, _ = self._pair()
        prefilter.reset_stats()
        items = make_items()
        filt.place_batch(items, make_cluster(LB_CAP + delta))
        st = prefilter.stats().get("drex_lb", {})
        if delta > 0:  # filter engages only when L > cap
            assert st["engaged"] == len(items)
            assert st["engaged"] == st["accepted"] + st["fallback"]
        else:
            assert st.get("engaged", 0) == 0


class TestLBFallback:
    def test_failed_sufficiency_rows_rerun_unfiltered(self):
        # Near-hopeless nodes + a hard target: the filtered grid's found
        # P hits the prefix's own min parity, the sufficiency test
        # fails, and every row must re-run over the full grid.
        filt = _tuned("drex_lb", PREFILTER_CAP=LB_CAP)
        oracle = _tuned("drex_lb", PREFILTER_CAP=LB_CAP)
        oracle.use_kernel = False
        rng = np.random.default_rng(3)
        cluster = ClusterView.from_nodes(
            [
                StorageNode(
                    node_id=i,
                    capacity_mb=5e4,
                    write_bw=float(rng.uniform(50, 400)),
                    read_bw=float(rng.uniform(50, 450)),
                    annual_failure_rate=float(rng.uniform(0.6, 0.95)),
                )
                for i in range(LB_CAP + 6)
            ]
        )
        items = make_items(4, target=0.999999)
        prefilter.reset_stats()
        got = filt.place_batch(items, cluster)
        st = prefilter.stats()["drex_lb"]
        assert st["fallback"] > 0, "setup no longer triggers the fallback lane"
        want = [oracle.place_scalar(it, cluster) for it in items]
        assert_decisions_match(got, want, "drex_lb fallback lane")


SC_BUDGET = 16  # instance override; sc_cap(16) == rung(17) == 24
SC_CAP = prefilter.sc_cap(SC_BUDGET)


@pytest.mark.parametrize("delta", [-1, 0, 1])
@pytest.mark.parametrize("ties", [False, True])
class TestSCBoundary:
    def test_matches_scalar_oracle_at_the_cut(self, delta, ties):
        filt = _tuned("drex_sc", MAX_MAPPINGS=SC_BUDGET)
        oracle = _tuned("drex_sc", MAX_MAPPINGS=SC_BUDGET)
        oracle.use_kernel = False
        cluster = make_cluster(SC_CAP + delta, ties=ties)
        items = make_items()
        prefilter.reset_stats()
        got = filt.place_batch(items, cluster)
        # Sequential scalar calls see the same running-smin anchors the
        # batch threads through (place_batch's documented semantics).
        want = [oracle.place_scalar(it, cluster) for it in items]
        assert_decisions_match(got, want, f"drex_sc at cap{delta:+d}")
        st = prefilter.stats().get("drex_sc", {})
        if delta > 0:
            # SC's slice is unconditionally exact: no fallback lane.
            assert st["engaged"] == st["accepted"] == len(items)
            assert st.get("fallback", 0) == 0
        else:
            assert st.get("engaged", 0) == 0


LU_CAP = 6  # SCAN_CAP override


@pytest.mark.parametrize("delta", [-1, 0, 1])
class TestLeastUsedBoundary:
    def test_matches_scalar_oracle_at_the_cut(self, delta):
        filt = _tuned("greedy_least_used", SCAN_CAP=LU_CAP)
        oracle = create_scheduler("greedy_least_used")
        oracle.use_kernel = False
        cluster = make_cluster(LU_CAP + delta)
        items = make_items()
        got = filt.place_batch(items, cluster)
        want = [oracle.place_scalar(it, cluster) for it in items]
        assert_decisions_match(got, want, f"greedy_least_used at cap{delta:+d}")

    def test_capped_scan_that_finds_nothing_falls_back(self, delta):
        # Impossible target: no N is feasible within the cap (nor at
        # all); the capped kernel must recover the oracle's rejection.
        filt = _tuned("greedy_least_used", SCAN_CAP=LU_CAP)
        oracle = create_scheduler("greedy_least_used")
        oracle.use_kernel = False
        cluster = make_cluster(LU_CAP + delta, afr_hi=0.9, seed=5)
        items = make_items(4, target=0.9999999)
        prefilter.reset_stats()
        got = filt.place_batch(items, cluster)
        want = [oracle.place_scalar(it, cluster) for it in items]
        assert_decisions_match(got, want, "greedy_least_used fallback")
        if delta > 0 and any(d.placement is None for d in got):
            assert prefilter.stats()["greedy_least_used"]["fallback"] > 0


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("name", ALL_REGISTERED)
class TestRegistrySweep:
    """Every batch-scoring scheduler, filtered caps engaged, vs its own
    sequential scalar oracle — decisions bit-identical."""

    #: small caps so the filter engages at sweep cluster sizes; applied
    #: to filtered and oracle instances alike (attribute-gated, so
    #: schedulers without a given knob are untouched).
    TUNING = {"PREFILTER_CAP": 8, "MAX_MAPPINGS": 8, "SCAN_CAP": 8}

    def _tune_if_present(self, sched):
        for attr, val in self.TUNING.items():
            if hasattr(type(sched), attr):
                setattr(sched, attr, val)
        for attr in ("KERNEL_MIN_NODES", "KERNEL_MIN_NODES_BATCH"):
            if hasattr(type(sched), attr):
                setattr(sched, attr, 0)
        return sched

    def test_filtered_batch_matches_scalar_oracle(self, name, seed):
        if not get_spec(name).capabilities.batch_scoring:
            pytest.skip("no batched scoring path")
        if not hasattr(create_scheduler(name), "place_scalar"):
            # e.g. test-helper registrations without a scalar oracle
            pytest.skip("no scalar-oracle API to compare against")
        rng = np.random.default_rng(seed)
        n = int(rng.integers(20, 41))
        cluster = make_cluster(n, seed=seed + 100)
        items = make_items(8, seed=seed + 200)
        filt = self._tune_if_present(create_scheduler(name))
        oracle = self._tune_if_present(create_scheduler(name))
        oracle.use_kernel = False
        prefilter.reset_stats()
        got = filt.place_batch(items, cluster)
        want = [oracle.place_scalar(it, cluster) for it in items]
        assert_decisions_match(got, want, f"{name} sweep seed={seed}")
        st = prefilter.stats().get(filt.name, {})
        if getattr(filt, "use_prefilter", False):
            # The tuned caps are below every sweep cluster size, so the
            # filtered lane must actually have run.
            assert st["engaged"] == len(items)
            assert st["engaged"] == st["accepted"] + st.get("fallback", 0)


class TestStatsAccounting:
    def test_record_validates_events(self):
        with pytest.raises(ValueError):
            prefilter.record("x", "nonsense")

    def test_record_accumulates_and_resets(self):
        prefilter.reset_stats()
        prefilter.record("x", "engaged", 3)
        prefilter.record("x", "engaged", 2)
        prefilter.record("x", "fallback")
        prefilter.record("x", "accepted", 0)  # no-op
        st = prefilter.stats()["x"]
        assert st["engaged"] == 5 and st["fallback"] == 1 and st["accepted"] == 0
        st["engaged"] = 999  # snapshot is a copy
        assert prefilter.stats()["x"]["engaged"] == 5
        prefilter.reset_stats()
        assert prefilter.stats() == {}

    def test_caps_are_shape_rungs(self):
        from repro.core import shapes

        assert prefilter.sc_cap(1024) == shapes.rung(1025)
        assert prefilter.sc_cap(1024) >= 1025
        assert prefilter.lb_cap() == shapes.rung(prefilter.lb_cap())
