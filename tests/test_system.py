"""End-to-end system behaviour tests: the paper's headline claims on
fast CPU-scaled workloads, and the full train→checkpoint→fail→restore
pipeline through the public API."""

import pytest

from _train_state import TrainingClient, states_equal, tiny_state
from repro.checkpoint import CheckpointPolicy, DRexCheckpointer, StorageFabric
from repro.core import create_scheduler
from repro.storage import make_node_set, make_trace, run_simulation

# full-pipeline e2e simulations: full lane only (deselect via -m "not slow").
pytestmark = pytest.mark.slow


SOTA = ["ec(3,2)", "ec(4,2)", "ec(6,3)", "daos"]


@pytest.fixture(scope="module")
def saturating_results():
    nodes = make_node_set("most_used", capacity_scale=0.001)
    cap = sum(n.capacity_mb for n in nodes)
    items = make_trace("meva", seed=0, total_mb=cap * 0.95)
    return {
        name: run_simulation(nodes, create_scheduler(name), items)
        for name in SOTA + ["drex_sc", "drex_lb", "greedy_min_storage", "greedy_least_used"]
    }


class TestPaperHeadlines:
    """§5 claims, structurally reproduced at CPU scale."""

    def test_drex_stores_more_than_sota_average(self, saturating_results):
        r = saturating_results
        avg_sota = sum(r[a].stored_mb for a in SOTA) / len(SOTA)
        assert r["drex_sc"].stored_mb > 1.15 * avg_sota
        assert r["drex_lb"].stored_mb > 1.10 * avg_sota

    def test_greedy_min_storage_stores_most(self, saturating_results):
        r = saturating_results
        best = max(v.stored_mb for v in r.values())
        assert r["greedy_min_storage"].stored_mb == pytest.approx(best, rel=0.02)

    def test_sc_nearly_matches_gms_with_better_throughput(self, saturating_results):
        r = saturating_results
        assert r["drex_sc"].stored_mb > 0.85 * r["greedy_min_storage"].stored_mb
        assert r["drex_sc"].throughput_mbps > r["greedy_min_storage"].throughput_mbps

    def test_static_ec_fails_extreme_reliability(self):
        """Fig. 5 'missing bars': fixed (K,P) can't reach 7 nines."""
        nodes = make_node_set("most_used", capacity_scale=0.001)
        items = make_trace("meva", seed=0, n_items=60, reliability=0.9999999)
        for algo in ("ec(3,2)", "ec(4,2)", "ec(6,3)"):
            res = run_simulation(nodes, create_scheduler(algo), items)
            assert res.n_stored == 0, algo
        res = run_simulation(nodes, create_scheduler("drex_sc"), items)
        assert res.n_stored == len(items)

    def test_dynamic_algorithms_survive_more_failures(self):
        """Fig. 12 pattern at RT 90%, non-saturating: 4 failures drawn by
        failure-rate weight (the paper's protocol). Dynamic reschedules
        retain ~everything; EC(6,3) needs 9 live nodes and collapses."""
        from repro.storage import SimConfig

        nodes = make_node_set("most_unreliable", capacity_scale=0.001)
        cap = sum(n.capacity_mb for n in nodes)
        items = make_trace("meva", seed=1, total_mb=cap * 0.15, reliability=0.9)
        sched = tuple((20.0 + 10 * i, -1) for i in range(4))  # weighted draws
        cfg = SimConfig(failure_schedule=sched, seed=1)
        dyn = run_simulation(nodes, create_scheduler("drex_sc"), items, cfg)
        assert dyn.retained_fraction > 0.95
        static = run_simulation(
            nodes, create_scheduler("ec(6,3)"), items, SimConfig(failure_schedule=sched, seed=1)
        )
        assert static.retained_fraction < 0.5
        assert dyn.retained_fraction > static.retained_fraction + 0.4


class TestFullPipeline:
    def test_train_checkpoint_fail_restore(self):
        """The whole stack, one story: a training job saves D-Rex EC
        checkpoints, storage nodes die, the state restores bit-exact."""
        fabric = StorageFabric(make_node_set("most_used", capacity_scale=1e-4))
        ck = DRexCheckpointer(
            fabric, "drex_sc",
            CheckpointPolicy(item_mb=0.5, reliability_target=0.99999,
                             retention_days=365.0),
        )
        job = TrainingClient(ck, every=4)
        state = job.run(job.init(), 0, 8)

        # two storage nodes die; the checkpoint must survive (P >= 2)
        fabric.fail_node(0)
        fabric.fail_node(4)
        restored, step = job.resume()
        assert step == 8
        assert states_equal(state, restored)

    def test_checkpoint_overhead_tracks_drex_placement(self):
        """The checkpointer's storage overhead equals N/K of the D-Rex
        placements it received (EC accounting is airtight end to end)."""
        state = tiny_state()
        fabric = StorageFabric(make_node_set("most_used", capacity_scale=1e-4))
        ck = DRexCheckpointer(fabric, "greedy_least_used", CheckpointPolicy(item_mb=0.25))
        man = ck.save(state, 1)
        ratios = []
        for meta in man["leaves"]:
            for g in meta["groups"]:
                ratios.append((g["k"] + g["p"]) / g["k"])
        got = ck.stats["bytes_stored"] / ck.stats["bytes_raw"]
        assert min(ratios) - 0.01 <= got <= max(ratios) + 0.35  # + bucket padding
