"""EC-protected checkpointing tests: save/restore, node failures, repair,
async path, GC, a training client's restart, elastic restore."""

import time

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from _train_state import TrainingClient, states_equal, tiny_state
from repro.checkpoint import CheckpointPolicy, DRexCheckpointer, StorageFabric
from repro.launch import make_local_mesh
from repro.storage import make_node_set

# checkpoint save/restore e2e: full lane only (deselect via -m "not slow").
pytestmark = pytest.mark.slow


def small_fabric(scale=1e-5):
    return StorageFabric(make_node_set("most_used", capacity_scale=scale))


class TestSaveRestore:
    @pytest.mark.parametrize("sched", ["drex_sc", "drex_lb", "greedy_min_storage", "greedy_least_used"])
    def test_roundtrip_all_schedulers(self, sched):
        state = tiny_state()
        ck = DRexCheckpointer(small_fabric(), sched, CheckpointPolicy(item_mb=0.25))
        ck.save(state, 1)
        restored, step = ck.restore_latest(state)
        assert step == 1
        assert states_equal(state, restored)

    def test_restore_after_p_failures(self):
        state = tiny_state()
        fabric = small_fabric()
        ck = DRexCheckpointer(fabric, "drex_sc", CheckpointPolicy(item_mb=0.25, reliability_target=0.999))
        ck.save(state, 5)
        fabric.fail_node(1)
        restored, _ = ck.restore_latest(state)
        assert states_equal(state, restored)

    def test_unrecoverable_when_too_many_failures(self):
        state = tiny_state()
        fabric = small_fabric()
        ck = DRexCheckpointer(fabric, "greedy_least_used", CheckpointPolicy(item_mb=0.25))
        ck.save(state, 5)
        for n in range(9):
            fabric.fail_node(n)
        with pytest.raises(IOError):
            ck.restore(5, state)

    def test_storage_overhead_below_replication(self):
        """EC beats the 3x replication of HDFS-style systems (paper §1)."""
        state = tiny_state()
        ck = DRexCheckpointer(small_fabric(), "drex_sc", CheckpointPolicy(item_mb=0.25))
        ck.save(state, 1)
        overhead = ck.stats["bytes_stored"] / ck.stats["bytes_raw"]
        assert 1.0 < overhead < 2.0

    def test_async_save(self):
        state = tiny_state()
        ck = DRexCheckpointer(small_fabric(), "drex_lb", CheckpointPolicy(item_mb=0.25))
        fut = ck.save_async(state, 7)
        man = fut.result(timeout=120)
        assert man["step"] == 7
        restored, step = ck.restore_latest(state)
        assert step == 7 and states_equal(state, restored)

    def test_gc_keeps_last_k(self):
        state = tiny_state()
        fabric = small_fabric()
        ck = DRexCheckpointer(fabric, "drex_lb", CheckpointPolicy(item_mb=0.25, keep_last=2))
        for s in (1, 2, 3):
            ck.save(state, s)
        assert sorted(ck._manifests) == [2, 3]
        # bytes for step 1 were actually deleted from the fabric
        used = fabric.cluster.used_mb.sum()
        ck.save(state, 4)
        assert fabric.cluster.used_mb.sum() == pytest.approx(used, rel=0.01)


class TestRepair:
    def test_repair_restores_reliability(self):
        state = tiny_state()
        fabric = small_fabric()
        ck = DRexCheckpointer(fabric, "drex_sc", CheckpointPolicy(item_mb=0.25, reliability_target=0.999))
        ck.save(state, 1)
        fabric.fail_node(0)
        degraded = min(ck.group_reliability())
        n = ck.repair()
        assert n > 0
        assert min(ck.group_reliability()) >= degraded
        restored, _ = ck.restore_latest(state)
        assert states_equal(state, restored)

    def test_repair_noop_when_healthy(self):
        state = tiny_state()
        ck = DRexCheckpointer(small_fabric(), "drex_sc", CheckpointPolicy(item_mb=0.25))
        ck.save(state, 1)
        assert ck.repair() == 0

    def test_repair_raises_instead_of_silently_under_repairing(self):
        """Regression: with fewer eligible live nodes than missing chunks
        the old ``zip(missing, live)`` truncated silently, leaving groups
        degraded with no error.  A 5-node fabric and EC(3,2) puts every
        group on all 5 nodes; after one failure there are zero candidate
        nodes, so strict repair must raise (and must not partially
        re-map), while strict=False reports 0 chunks rebuilt."""
        state = tiny_state()
        fabric = StorageFabric(make_node_set("most_used", capacity_scale=1e-5)[:5])
        ck = DRexCheckpointer(
            fabric, "ec(3,2)",
            CheckpointPolicy(item_mb=0.25, reliability_target=0.9),
        )
        ck.save(state, 1)
        node_ids_before = [
            tuple(gd["node_ids"])
            for meta in ck._manifests[1]["leaves"] if meta is not None
            for gd in meta["groups"]
        ]
        fabric.fail_node(0)
        with pytest.raises(IOError, match="degraded"):
            ck.repair()
        assert ck.repair(strict=False) == 0
        # No partial re-mapping happened behind the error.
        node_ids_after = [
            tuple(gd["node_ids"])
            for meta in ck._manifests[1]["leaves"] if meta is not None
            for gd in meta["groups"]
        ]
        assert node_ids_after == node_ids_before
        # The data itself is still within P: restore works regardless.
        restored, _ = ck.restore_latest(state)
        assert states_equal(state, restored)

    def test_repaired_chunks_match_surviving_shape(self):
        """Regression: repair must re-encode the bucket-padded payload —
        otherwise replacement chunks differ in shape from survivors and
        restore fails on groups whose size is not a power of two."""
        state = tiny_state()
        fabric = small_fabric()
        ck = DRexCheckpointer(fabric, "drex_lb", CheckpointPolicy(item_mb=0.25))
        ck.save(state, 1)
        # Fail every node that holds row 0 of some group, so restore must
        # read at least one repaired chunk alongside surviving ones.
        first_row_nodes = {
            meta["groups"][0]["node_ids"][0]
            for meta in ck._manifests[1]["leaves"]
            if meta is not None
        }
        for n in list(first_row_nodes)[:2]:
            fabric.fail_node(n)
        assert ck.repair() > 0
        restored, _ = ck.restore_latest(state)
        assert states_equal(state, restored)


class TestPipeline:
    """The streaming encode→place→write pipeline must be observationally
    identical to the serial path — same placements, same restored bytes —
    and overlapping async saves must not deadlock or corrupt stats."""

    def _placements(self, ck, step):
        return [
            (gd["key"], gd["k"], gd["p"], tuple(gd["node_ids"]))
            for meta in ck._manifests[step]["leaves"] if meta is not None
            for gd in meta["groups"]
        ]

    @pytest.mark.parametrize("wave", [1, 3, 16])
    def test_pipelined_matches_serial(self, wave):
        state = tiny_state()
        cks = {}
        for workers in (0, 2):
            ck = DRexCheckpointer(
                small_fabric(), "drex_sc",
                CheckpointPolicy(item_mb=0.25, pipeline_workers=workers,
                                 encode_wave_groups=wave),
            )
            ck.save(state, 1)
            cks[workers] = ck
        assert self._placements(cks[0], 1) == self._placements(cks[2], 1)
        assert cks[0].stats["bytes_stored"] == cks[2].stats["bytes_stored"]
        restored, _ = cks[2].restore_latest(state)
        assert states_equal(state, restored)

    def test_pipelined_respects_link_bandwidth_fabric(self):
        """Puts through a bandwidth-simulating fabric still land intact."""
        state = tiny_state()
        fabric = StorageFabric(
            make_node_set("most_used", capacity_scale=1e-5), link_mbps=2000.0
        )
        ck = DRexCheckpointer(fabric, "drex_lb", CheckpointPolicy(
            item_mb=0.25, pipeline_workers=2, encode_wave_groups=2))
        ck.save(state, 1)
        restored, _ = ck.restore_latest(state)
        assert states_equal(state, restored)

    def test_overlapping_async_saves(self):
        """Two save_async calls in flight at once: both complete (drivers
        and I/O run on separate pools, so no cross-wait deadlock) and
        both checkpoints restore bit-exact."""
        state = tiny_state()
        ck = DRexCheckpointer(
            small_fabric(), "drex_lb",
            CheckpointPolicy(item_mb=0.25, keep_last=2, pipeline_workers=2,
                             encode_wave_groups=2),
        )
        futs = [ck.save_async(state, s) for s in (1, 2)]
        for f, step in zip(futs, (1, 2)):
            assert f.result(timeout=120)["step"] == step
        assert sorted(ck._manifests) == [1, 2]
        for step in (1, 2):
            assert states_equal(state, ck.restore(step, state))

    def test_mid_pipeline_put_failure_propagates(self):
        """A fabric error inside a background put wave surfaces as the
        save's exception (no hang, no orphaned futures), and the
        checkpointer stays usable for a later save."""
        state = tiny_state()
        fabric = StorageFabric(make_node_set("most_used", capacity_scale=1e-9))
        ck = DRexCheckpointer(fabric, "drex_sc", CheckpointPolicy(
            item_mb=0.25, pipeline_workers=2, encode_wave_groups=2))
        with pytest.raises(IOError):
            ck.save(state, 1)
        assert 1 not in ck._manifests
        # pools survive the failure: a save against a healthy fabric works
        ck2 = DRexCheckpointer(small_fabric(), "drex_sc",
                               CheckpointPolicy(item_mb=0.25))
        ck2.save(state, 2)
        restored, _ = ck2.restore_latest(state)
        assert states_equal(state, restored)

    @pytest.mark.parametrize("put_fails", [False, True], ids=["puts_land", "put_raises"])
    def test_wave_buffers_reused_only_after_their_puts(self, monkeypatch, put_fails):
        """Puts that sleep keep two waves in flight while the next one is
        encoded: every stored blob must still be the chunk a fresh
        per-group encode makes (no put reads a later wave's bytes), no
        wave buffer is allocated after the first save, and the free list
        never holds more than three.  With a put that raises in the middle
        save, every buffer still comes back."""
        from repro import telemetry
        from repro.checkpoint import manager
        from repro.ec import ECCodec

        state = tiny_state()
        fabric = small_fabric()
        ck = DRexCheckpointer(fabric, "drex_sc", CheckpointPolicy(
            item_mb=0.25, pipeline_workers=2, encode_wave_groups=2))
        real_put, calls, held = StorageFabric.put, [0], []

        def slow_put(self, node, key, blob):
            time.sleep(0.002)
            calls[0] += 1
            if put_fails and key.startswith("ck102_") and calls[0] % 40 == 0:
                raise IOError("planted put failure")
            return real_put(self, node, key, blob)

        real_give = manager._WaveBuffers.give

        def give(bufs, buf):
            real_give(bufs, buf)
            held.append(len(bufs._free))

        monkeypatch.setattr(StorageFabric, "put", slow_put)
        monkeypatch.setattr(manager._WaveBuffers, "give", give)
        telemetry.reset(prefilter_counters=False, matrix_caches=False, compile_census=False)
        leaves = [np.asarray(x).tobytes() for x in jax.tree.leaves(state)]
        max_bytes = int(ck.policy.item_mb * 1e6)
        for step in (101, 102, 103):
            if put_fails and step == 102:
                with pytest.raises(IOError, match="planted"):
                    ck.save(state, step)
                assert len(ck._wave_bufs._free) == 3  # all came back
                continue
            manifest = ck.save(state, step)
            n_groups = 0
            for raw, meta in zip(leaves, manifest["leaves"]):
                for part, g in enumerate(meta["groups"]):
                    payload = raw[part * max_bytes : (part + 1) * max_bytes]
                    want = ECCodec(g["k"], g["p"]).encode(manager._pad_to_bucket(payload))
                    for row, node in enumerate(g["node_ids"]):
                        blob = fabric.get(node, f"{g['key']}_r{row}")
                        np.testing.assert_array_equal(np.frombuffer(blob, np.uint8), want[row])
                    n_groups += 1
            assert n_groups > 6  # four or more waves: the buffers cycle
        per = {r["request"]: r["spans"] for r in telemetry.span_stats()["requests"]}
        assert per[101]["codec.wave_alloc"]["count"] >= 3
        for step in (102, 103):
            assert "codec.wave_alloc" not in per[step]
        assert per[103]["codec.wave_buffer"]["count"] > 3  # the buffers cycled
        assert held and max(held) <= 3


class TestKernelVsRefCodecs:
    def test_checkpoint_identical_between_codecs(self):
        state = tiny_state()
        for use_kernel in (True, False):
            ck = DRexCheckpointer(
                small_fabric(), "drex_lb",
                CheckpointPolicy(item_mb=0.25, use_kernel=use_kernel),
            )
            ck.save(state, 1)
            restored, _ = ck.restore_latest(state)
            assert states_equal(state, restored)


class TestTrainerIntegration:
    def test_checkpoint_restart_continues_training(self):
        """Kill-and-restart: a run restored from its newest checkpoint
        continues exactly like an uninterrupted one."""
        ck = DRexCheckpointer(small_fabric(), "drex_sc", CheckpointPolicy(item_mb=0.25))
        job = TrainingClient(ck, every=3)
        job.run(job.init(), 0, 7)  # dies after step 7
        assert sorted(ck._manifests) == [3, 6]

        # a fresh client restarts and resumes from step 6
        restarted = TrainingClient(ck, every=3)
        resumed, step = restarted.resume()
        assert step == 6
        assert states_equal(resumed, TrainingClient().run(job.init(), 0, 6))
        continued = restarted.run(resumed, step, 10)
        uninterrupted = TrainingClient().run(job.init(), 0, 10)
        assert int(continued["opt"]["step"]) == 10
        assert states_equal(continued, uninterrupted)

    def test_elastic_restore_onto_new_mesh(self):
        state = tiny_state()
        ck = DRexCheckpointer(small_fabric(), "drex_sc", CheckpointPolicy(item_mb=0.25))
        ck.save(state, 1)
        restored, _ = ck.restore_latest(state)

        mesh = make_local_mesh(1, 1)  # "new" cluster shape

        def place(x):
            spec = PartitionSpec("data") if x.ndim else PartitionSpec()
            return jax.device_put(x, NamedSharding(mesh, spec))

        resharded = jax.tree.map(place, restored)
        for leaf in jax.tree.leaves(resharded):
            assert isinstance(leaf, jax.Array) and leaf.sharding.mesh == mesh
        assert states_equal(state, resharded)
