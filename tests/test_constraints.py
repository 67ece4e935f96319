"""Failure-domain constraint API: ``PlacementConstraints`` validation,
the cap-admitted candidate order, the swap post-pass, the registry
capability query, engine threading, and the telemetry facade."""

import numpy as np
import pytest

from repro import telemetry
from repro.core import (
    ClusterView,
    DataItem,
    PlacementConstraints,
    PlacementEngine,
    StorageNode,
    create_scheduler,
    find,
)
from repro.core import constraints as cmod
from repro.core.types import Placement


def topo_nodes(n, n_racks, cap=1e5, racks_per_zone=2):
    return [
        StorageNode(
            node_id=i,
            capacity_mb=cap,
            write_bw=200.0,
            read_bw=250.0,
            annual_failure_rate=0.01,
            rack=i % n_racks,
            zone=(i % n_racks) // racks_per_zone,
        )
        for i in range(n)
    ]


def mk_item(iid=0, size=50.0, rt=0.9):
    return DataItem(iid, size, 0.0, 365.0, rt)


class TestPlacementConstraints:
    def test_defaults_are_unconstrained(self):
        c = PlacementConstraints()
        assert c.unconstrained

    def test_any_field_clears_unconstrained(self):
        assert not PlacementConstraints(max_per_rack=2).unconstrained
        assert not PlacementConstraints(min_zones=2).unconstrained

    @pytest.mark.parametrize(
        "kw",
        [
            {"max_per_rack": 0},
            {"max_per_zone": -1},
            {"min_racks": 0},
            {"min_zones": -2},
        ],
    )
    def test_invalid_values_rejected(self, kw):
        with pytest.raises(ValueError):
            PlacementConstraints(**kw)

    def test_satisfied_by_checks_caps_and_spread(self):
        rack = np.array([0, 0, 1, 1, 2])
        zone = np.array([0, 0, 0, 1, 1])
        c = PlacementConstraints(max_per_rack=2, min_racks=2, min_zones=2)
        assert c.satisfied_by([0, 2, 3], rack, zone)
        assert not c.satisfied_by([0, 1, 2], rack, zone)  # zone spread
        assert not PlacementConstraints(max_per_rack=1).satisfied_by(
            [0, 1], rack, zone
        )

    def test_spread_clamps_to_mapping_size(self):
        # min_racks=4 on a 2-chunk mapping: need min(4, 2) = 2 racks.
        rack = np.array([0, 1, 2, 3])
        zone = np.zeros(4, dtype=np.int64)
        c = PlacementConstraints(min_racks=4)
        assert c.satisfied_by([0, 1], rack, zone)
        assert not c.satisfied_by([0, 0], np.array([5, 5]), np.zeros(2))


class TestConstrainedOrder:
    RACK = np.array([0, 0, 0, 1, 1, 2])
    ZONE = np.array([0, 0, 0, 0, 1, 1])

    def test_no_caps_returns_same_object(self):
        order = np.array([3, 1, 2])
        out = cmod.constrained_order(
            order, self.RACK, self.ZONE, PlacementConstraints(min_racks=3)
        )
        assert out is order
        assert cmod.constrained_order(order, self.RACK, self.ZONE, None) is order

    def test_rack_cap_admits_in_order(self):
        order = np.array([0, 1, 2, 3, 4, 5])
        out = cmod.constrained_order(
            order, self.RACK, self.ZONE, PlacementConstraints(max_per_rack=2)
        )
        # node 2 (third of rack 0) dropped, everything else kept in order.
        np.testing.assert_array_equal(out, [0, 1, 3, 4, 5])

    def test_dual_caps_rack_reject_frees_no_zone_slot(self):
        # Node 2 is rack-rejected; it must not consume a zone-0 slot,
        # so node 3 (zone 0) is still admitted.
        out = cmod.constrained_order(
            np.arange(6),
            self.RACK,
            self.ZONE,
            PlacementConstraints(max_per_rack=2, max_per_zone=3),
        )
        np.testing.assert_array_equal(out, [0, 1, 3, 4, 5])

    def test_admitted_set_subsets_conform(self):
        import itertools

        rng = np.random.default_rng(0)
        rack = rng.integers(0, 4, size=20)
        zone = rng.integers(0, 3, size=20)
        c = PlacementConstraints(max_per_rack=2, max_per_zone=3)
        out = cmod.constrained_order(np.arange(20), rack, zone, c)
        for r in (2, min(4, len(out))):
            for combo in itertools.islice(itertools.combinations(out, r), 50):
                assert c.satisfied_by(list(combo), rack, zone)


class TestRepairMapping:
    def _cluster(self, n=12, n_racks=4):
        return ClusterView.from_nodes(topo_nodes(n, n_racks))

    def test_conforming_mapping_returned_unchanged(self):
        cl = self._cluster()
        pl = Placement(k=2, p=1, node_ids=(0, 1, 2))  # racks 0,1,2
        c = PlacementConstraints(max_per_rack=1, min_racks=2)
        got = cmod.repair_mapping(pl, cl, c, 10.0)
        assert got is not None and got[0] is pl and got[1] == 0

    def test_over_cap_chunk_swapped_out_of_domain(self):
        cl = self._cluster()
        # Nodes 0, 4, 8 are all rack 0.
        pl = Placement(k=2, p=1, node_ids=(0, 4, 8))
        c = PlacementConstraints(max_per_rack=2)
        got = cmod.repair_mapping(pl, cl, c, 10.0)
        assert got is not None
        new_pl, swaps = got
        assert swaps == 1
        assert c.satisfied_by(new_pl.node_ids, cl.rack, cl.zone)
        assert len(set(new_pl.node_ids)) == 3

    def test_spread_promotion(self):
        cl = self._cluster()
        pl = Placement(k=2, p=1, node_ids=(0, 4, 8))  # one rack
        c = PlacementConstraints(min_racks=3)
        got = cmod.repair_mapping(pl, cl, c, 10.0)
        assert got is not None
        ids = got[0].node_ids
        assert len(set(int(cl.rack[i]) for i in ids)) >= 3

    def test_infeasible_returns_none(self):
        cl = ClusterView.from_nodes(topo_nodes(4, 1))  # one rack only
        pl = Placement(k=2, p=1, node_ids=(0, 1, 2))
        got = cmod.repair_mapping(
            pl, cl, PlacementConstraints(min_racks=2), 10.0
        )
        assert got is None

    def test_reliability_recheck_can_reject_swaps(self):
        cl = self._cluster()
        pl = Placement(k=2, p=1, node_ids=(0, 4, 8))
        c = PlacementConstraints(max_per_rack=1)
        got = cmod.repair_mapping(
            pl, cl, c, 10.0,
            min_parity=lambda fp: pl.p + 1,  # target now unreachable
            fail_probs=cl.fail_probs(365.0),
        )
        assert got is None


class TestRegistryFind:
    def test_flags_filter_and_sort(self):
        topo = find(topology_aware=True)
        names = [s.name for s in topo]
        assert names == sorted(names)
        assert {"drex_sc", "drex_lb", "greedy_least_used",
                "greedy_min_storage"} <= set(names)

    def test_dict_and_kwargs_agree(self):
        assert [s.name for s in find(capabilities={"batch_scoring": True})] == [
            s.name for s in find(batch_scoring=True)
        ]

    def test_unknown_flag_raises(self):
        with pytest.raises(ValueError, match="unknown capability"):
            find(zone_aware=True)

    def test_no_filter_returns_everything(self):
        all_specs = find()
        assert {"daos", "random_spread", "drex_sc"} <= {
            s.name for s in all_specs
        }

    def test_make_scheduler_shim_is_gone(self):
        import repro.core as core

        assert not hasattr(core, "make_scheduler")
        with pytest.raises(ImportError):
            from repro.core.algorithms import make_scheduler  # noqa: F401


class TestEngineConstraintThreading:
    C = PlacementConstraints(max_per_rack=2, min_racks=2)

    def _engine(self, name, **kw):
        return PlacementEngine(
            ClusterView.from_nodes(topo_nodes(12, 4)),
            create_scheduler(name),
            **kw,
        )

    def test_topology_aware_places_with_zero_swaps(self):
        engine = self._engine("drex_sc", constraints=self.C)
        recs = [engine.place(mk_item(i)) for i in range(4)]
        assert all(r.ok for r in recs)
        for r in recs:
            assert self.C.satisfied_by(
                r.placement.node_ids, engine.cluster.rack, engine.cluster.zone
            )
        assert engine.stats["n_constraint_swaps"] == 0  # by construction

    def test_non_declaring_scheduler_fixed_by_post_pass(self):
        # 6 racks x cap 2 = 12 slots: room for random_spread's 9-chunk
        # EC(6,3) mappings after the post-pass reshuffles them.
        engine = PlacementEngine(
            ClusterView.from_nodes(topo_nodes(18, 6)),
            create_scheduler("random_spread"),
            constraints=self.C,
        )
        placed = [r for r in (engine.place(mk_item(i)) for i in range(8)) if r.ok]
        assert placed, "random_spread placed nothing on 18 nodes"
        for r in placed:
            assert self.C.satisfied_by(
                r.placement.node_ids, engine.cluster.rack, engine.cluster.zone
            )

    def test_per_call_constraints_override_engine_default(self):
        engine = self._engine("drex_lb")  # engine-level: unconstrained
        rec = engine.place(mk_item(), constraints=self.C)
        assert rec.ok
        assert self.C.satisfied_by(
            rec.placement.node_ids, engine.cluster.rack, engine.cluster.zone
        )

    def test_unsatisfiable_constraint_rejects_and_counts(self):
        tight = PlacementConstraints(max_per_rack=1, max_per_zone=1)
        # One zone only: any mapping >= 2 chunks violates the zone cap.
        engine = PlacementEngine(
            ClusterView.from_nodes(topo_nodes(12, 3, racks_per_zone=3)),
            create_scheduler("random_spread"),
            constraints=tight,
        )
        recs = [engine.place(mk_item(i)) for i in range(3)]
        assert all(not r.ok for r in recs)
        assert engine.stats["n_constraint_rejects"] == 3
        assert all("failure-domain" in r.reason for r in recs)

    def test_place_many_conforms_batch_and_sequential(self):
        for name in ("drex_lb", "daos"):
            engine = self._engine(name, constraints=self.C)
            recs = engine.place_many([mk_item(i) for i in range(5)])
            for r in recs:
                if r.ok:
                    assert self.C.satisfied_by(
                        r.placement.node_ids,
                        engine.cluster.rack,
                        engine.cluster.zone,
                    )

    def test_post_pass_swaps_are_counted(self):
        # Single-rack-heavy mapping forces the swap post-pass: daos packs
        # the fastest nodes, which here all share rack 0.
        # 8 nodes crowd rack 0; racks 1-4 hold two each (10 cap-2 slots,
        # enough for random_spread's 9-chunk mappings after swapping).
        nodes = topo_nodes(16, 1)
        for n in nodes:
            n.rack = 0 if n.node_id < 8 else 1 + (n.node_id % 4)
            n.zone = 0
        engine = PlacementEngine(
            ClusterView.from_nodes(nodes),
            create_scheduler("random_spread"),
            constraints=PlacementConstraints(max_per_rack=2),
        )
        placed = [r for r in (engine.place(mk_item(i)) for i in range(8)) if r.ok]
        assert placed
        assert engine.stats["n_constraint_swaps"] > 0


class TestTelemetryFacade:
    def test_snapshot_schema_matches_sources(self):
        from repro.core import prefilter, shapes
        from repro.kernels import ops as kops

        from repro.core import jitcache

        snap = telemetry.snapshot()
        assert snap.engine is None
        assert set(snap.matrix_cache) == set(kops.matrix_cache_stats())
        assert set(snap.compile_cache) == set(shapes.compile_cache_stats())
        assert snap.prefilter == prefilter.stats()
        assert set(snap.jit_cache) == set(jitcache.status())
        d = snap.as_dict()
        assert set(d) == {
            "prefilter",
            "matrix_cache",
            "compile_cache",
            "engine",
            "jit_cache",
            "spans",
        }

    def test_snapshot_includes_engine_counters(self):
        engine = PlacementEngine(
            ClusterView.from_nodes(topo_nodes(6, 3)),
            create_scheduler("drex_lb"),
        )
        engine.place(mk_item())
        snap = telemetry.snapshot(engine=engine)
        assert snap.engine["n_placed"] == 1
        # A copy, not an alias.
        snap.engine["n_placed"] = 99
        assert engine.stats["n_placed"] == 1

    def test_reset_zeroes_prefilter_counters(self):
        from repro.core import prefilter

        prefilter.record("drex_sc", "engaged", 3)
        assert telemetry.snapshot().prefilter
        telemetry.reset(matrix_caches=False, compile_census=False)
        assert telemetry.snapshot().prefilter == {}
