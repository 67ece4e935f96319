"""D-Rex-protected distributed checkpointing (the paper's technique as a
first-class framework feature).

Every checkpoint is cut into ~item_mb groups; each group is a D-Rex
"data item": the configured scheduler picks (K, P, M) per group against
the live heterogeneous fabric (reliability target + retention window are
checkpoint policy), the Cauchy-RS kernel encodes, and chunks land on the
chosen nodes.  Restore tolerates up to P node losses per group; `repair`
proactively re-encodes degraded groups after failures (§2
failure-recovery techniques layer on the paper's placement model
unchanged).

``save`` is a streaming encode→place→write pipeline: all groups of a
checkpoint are placed in ONE ``place_many`` batch (one shared
``BatchContext``, so the reliability DP amortizes across every group),
encoded in per-(K, P) cohort waves through ``ECCodec.encode_many`` (one
kernel launch per wave), and each wave's fabric ``put`` overlaps the
*next* wave's encode through a multi-worker I/O pool (double-buffered —
at most two waves of chunks are in flight, bounding peak memory).  Each
wave is staged, coded and put from one host wave buffer that the
checkpointer reuses from wave to wave and from save to save.
``pipeline_workers=0`` recovers the legacy serial path (per-group encode
then put), which benchmarks/fig13 uses as the upload baseline.

The manifest is mesh-agnostic (leaf shapes/dtypes + tree structure), so
restore composes with elastic rescale: `restore_latest` returns host
arrays that `jax.device_put` places on any mesh.
"""

from __future__ import annotations

import dataclasses
import io
import json
import threading
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Optional

import jax
import numpy as np

from repro import telemetry
from repro.core import BatchContext, DataItem, Placement, PlacementEngine, Scheduler
from repro.ec import ECCodec, plan_cohorts

from .fabric import StorageFabric

__all__ = ["CheckpointPolicy", "DRexCheckpointer"]

#: any pytree of arrays, saved leaf by leaf.
PyTree = Any


@dataclasses.dataclass(frozen=True)
class CheckpointPolicy:
    reliability_target: float = 0.999
    retention_days: float = 30.0
    item_mb: float = 64.0            # max group payload size
    use_kernel: bool = True          # Pallas/XLA bit-matrix codec vs ref
    keep_last: int = 2               # garbage-collect older checkpoints
    #: fabric-write workers for the save pipeline; 0 = legacy serial
    #: (per-group encode then put, no overlap — the fig13 baseline).
    pipeline_workers: int = 2
    #: max groups fused into one encode launch; also the wave size the
    #: pipeline double-buffers (bounds peak chunk memory to ~2 waves).
    #: The checkpointer keeps up to three wave buffers (two waves in
    #: flight, one being encoded), each of the largest wave's N x W bytes,
    #: alive between saves; before, a save allocated as much afresh and
    #: freed it.  A wave is at most this many groups x the bucket of
    #: ``item_mb`` x N/K: 3 x 1.61 GB held at the defaults and (K, P) =
    #: (4, 2).  One chip's shard of 8-way FSDP Yi-6B (groups of at most a
    #: 32 MiB bucket) holds 3 x 358.6 MB.
    encode_wave_groups: int = 16


#: waves whose fabric puts may be in flight while the next one encodes.
_WAVES_IN_FLIGHT = 2


class _WaveBuffers:
    """Free list of the host wave buffers ``ECCodec.encode_many`` stages,
    codes and assembles a wave in.  A buffer is handed back only once
    nothing reads it any more (its wave's puts are done); the list keeps
    at most ``_WAVES_IN_FLIGHT + 1`` of them, each of at least the largest
    wave seen, so a save shaped like an earlier one allocates nothing."""

    def __init__(self):
        self._lock = threading.Lock()
        self._free: list[np.ndarray] = []
        self._nbytes = 0

    def reserve(self, nbytes: int) -> None:
        """Size every later allocation to at least ``nbytes``."""
        with self._lock:
            self._nbytes = max(self._nbytes, nbytes)

    def take(self, nbytes: int) -> np.ndarray:
        with telemetry.span("codec.wave_buffer"):
            with self._lock:
                while self._free:
                    buf = self._free.pop()
                    if buf.size >= nbytes:
                        return buf
                size = max(self._nbytes, nbytes)
            with telemetry.span("codec.wave_alloc"):
                return np.empty(size, dtype=np.uint8)

    def give(self, buf: np.ndarray) -> None:
        with self._lock:
            if len(self._free) <= _WAVES_IN_FLIGHT and buf.size >= self._nbytes:
                self._free.append(buf)


@dataclasses.dataclass
class _Group:
    key: str
    k: int
    p: int
    node_ids: list
    orig_nbytes: int


def _pad_to_bucket(payload: bytes) -> bytes:
    """Pad to power-of-two bucket sizes so the codec sees a bounded set of
    chunk shapes (one jit compile per (K, P, bucket) instead of one per
    group) — steady-state encode throughput, <=2x padding on the tail
    group only.  Every (re-)encode of a group MUST go through this so
    repaired chunks keep the shape of the surviving ones."""
    bucket = 4096
    while bucket < len(payload):
        bucket <<= 1
    return payload + b"\x00" * (bucket - len(payload))


class DRexCheckpointer:
    def __init__(
        self,
        fabric: StorageFabric,
        scheduler: Scheduler | str = "drex_sc",
        policy: CheckpointPolicy | None = None,
    ):
        self.fabric = fabric
        # auto_commit=False: the fabric is the byte-accounting authority —
        # occupancy updates when chunks actually land (fabric.put), not at
        # decision time.
        self.engine = PlacementEngine(fabric.cluster, scheduler, auto_commit=False)
        self.scheduler = self.engine.scheduler
        self.policy = policy or CheckpointPolicy()
        self._manifests: dict[int, dict] = {}
        # Two pools, no cross-wait cycle: save drivers (async snapshots)
        # wait only on I/O futures, never on other drivers — so two
        # overlapping save_async calls cannot deadlock and no longer
        # serialize behind a single worker.
        self._save_pool = ThreadPoolExecutor(max_workers=2)
        self._io_pool = ThreadPoolExecutor(
            max_workers=max(1, self.policy.pipeline_workers)
        )
        #: serializes the placement phase (engine + item-id counter) so
        #: concurrent saves see consistent cluster snapshots.
        self._place_lock = threading.Lock()
        self._meta_lock = threading.Lock()
        self._item_counter = 0
        self._wave_bufs = _WaveBuffers()
        self.stats: dict[str, float] = {
            "bytes_raw": 0.0, "bytes_stored": 0.0, "encode_s": 0.0, "place_s": 0.0,
        }

    # -- save -------------------------------------------------------------------

    def save(self, state: PyTree, step: int) -> dict:
        """Encode→place→write one checkpoint through the batched pipeline.

        Placement decisions for all groups are made against the cluster
        view at the start of the save (one ``place_many`` batch) — the
        fabric's byte accounting still updates as chunks land.

        Each stage is a :func:`repro.telemetry.span` whose request is
        ``step`` (README "Telemetry facade" lists them)."""
        with telemetry.span("ckpt.save", request=step) as sp:
            return self._save(state, step, sp)

    def _save(self, state: PyTree, step: int, save_span) -> dict:
        leaves, treedef = jax.tree.flatten(state)
        # The tree structure is reconstructed from a like-state at restore
        # (shapes/dtypes per leaf live in the manifest).
        manifest: dict[str, Any] = {"step": step, "leaves": []}
        policy = self.policy
        max_bytes = int(policy.item_mb * 1e6)
        # 1. Split every leaf into group payloads (bucket-padded).
        payloads: list[bytes] = []
        orig_lens: list[int] = []
        slots: list[tuple[int, int]] = []  # (leaf_i, part)
        for li, leaf in enumerate(leaves):
            if leaf is None:
                manifest["leaves"].append(None)
                continue
            with telemetry.span("ckpt.d2h") as sp:
                arr = np.asarray(jax.device_get(leaf))
                # a host leaf (save_async hands those in) is not copied
                sp.nbytes = arr.nbytes if isinstance(leaf, jax.Array) else 0
            save_span.nbytes += arr.nbytes
            manifest["leaves"].append(
                {"shape": list(arr.shape), "dtype": str(arr.dtype), "groups": []}
            )
            with telemetry.span("ckpt.split") as sp:
                raw = arr.tobytes()
                sp.nbytes = len(raw)
                for off in range(0, max(len(raw), 1), max_bytes):
                    payload = raw[off : off + max_bytes]
                    padded = _pad_to_bucket(payload)
                    payloads.append(padded)
                    orig_lens.append(len(payload))
                    slots.append((li, off // max_bytes))
                    # a slice of all of ``raw`` is ``raw`` itself; padding
                    # writes the zero filler and then the padded payload
                    if payload is not raw:
                        sp.nbytes += len(payload)
                    if padded is not payload:
                        sp.nbytes += 2 * len(padded) - len(payload)
            with self._meta_lock:
                self.stats["bytes_raw"] += len(raw)
        # 2. One placement batch: groups share retention and reliability
        # target, so the engine's batch context amortizes the scheduler's
        # reliability DP across all groups of this save.
        with telemetry.span("ckpt.place"), self._place_lock:
            items = []
            for payload in payloads:
                self._item_counter += 1
                items.append(DataItem(
                    item_id=self._item_counter,
                    size_mb=max(len(payload) / 1e6, 1e-6),
                    arrival_time=float(step),
                    delta_t_days=policy.retention_days,
                    reliability_target=policy.reliability_target,
                ))
            records = self.engine.place_many(items, ctx=BatchContext())
        placements: list[Placement] = []
        for item, record in zip(items, records):
            with self._meta_lock:
                self.stats["place_s"] += record.overhead_s
            if record.placement is None:
                raise IOError(
                    f"D-Rex could not place checkpoint group "
                    f"({item.size_mb:.1f} MB, "
                    f"RT={policy.reliability_target}): {record.reason}"
                )
            placements.append(record.placement)
        # 3. Cohort waves: encode cohort i+1 while cohort i's chunks land.
        groups: list[Optional[_Group]] = [None] * len(payloads)
        wave_size = 1 if policy.pipeline_workers == 0 else max(
            1, policy.encode_wave_groups
        )
        waves: list[list[int]] = []
        for (_kp, idxs) in plan_cohorts([(pl.k, pl.p) for pl in placements]):
            for w in range(0, len(idxs), wave_size):
                waves.append(idxs[w : w + wave_size])
        pending: deque[tuple[Future, np.ndarray]] = deque()
        try:
            self._encode_waves(
                waves, payloads, placements, slots, orig_lens, groups,
                step, pending,
            )
            with telemetry.span("ckpt.put_wait"):
                while pending:
                    self._settle(pending.popleft())
        except BaseException:
            while pending:  # no orphaned background puts behind an error
                try:
                    self._settle(pending.popleft())
                except Exception:
                    pass
            raise
        # 4. Manifest in original (leaf, part) order.
        for g, (li, _part) in zip(groups, slots):
            manifest["leaves"][li]["groups"].append(dataclasses.asdict(g))
        with self._meta_lock:
            self._manifests[step] = manifest
        self._gc(step)
        return manifest

    def _encode_waves(
        self, waves, payloads, placements, slots, orig_lens, groups,
        step, pending,
    ) -> None:
        """Encode each wave and hand its chunks to the I/O pool.  A wave's
        buffer goes back to the free list when its puts are done."""
        policy = self.policy
        codecs = [
            ECCodec(placements[w[0]].k, placements[w[0]].p, use_kernel=policy.use_kernel)
            for w in waves
        ]
        sizes = [
            codec.wave_nbytes([len(payloads[i]) for i in wave])
            for codec, wave in zip(codecs, waves)
        ]
        self._wave_bufs.reserve(max(sizes, default=0))
        for wave, codec, nbytes in zip(waves, codecs, sizes):
            k, p = codec.k, codec.p
            with telemetry.span("ckpt.encode") as sp:
                buf = self._wave_bufs.take(nbytes)
                # a buffer whose encode failed is dropped, not reused: a
                # device array made from it may still read it
                chunk_mats = codec.encode_many([payloads[i] for i in wave], out=buf)
            with self._meta_lock:
                self.stats["encode_s"] += sp.seconds
            entries = []
            for i, chunks in zip(wave, chunk_mats):
                li, part = slots[i]
                g = _Group(
                    key=f"ck{step}_l{li}_p{part}", k=k, p=p,
                    node_ids=list(placements[i].node_ids),
                    orig_nbytes=orig_lens[i],
                )
                groups[i] = g
                entries.append((g, chunks))
            if policy.pipeline_workers == 0:
                try:
                    self._put_wave(entries, step)
                finally:
                    self._wave_bufs.give(buf)
            else:
                pending.append((self._io_pool.submit(self._put_wave, entries, step), buf))
                # double buffer: at most 2 waves of chunks in flight
                if len(pending) > _WAVES_IN_FLIGHT:
                    with telemetry.span("ckpt.put_wait"):
                        while len(pending) > _WAVES_IN_FLIGHT:
                            self._settle(pending.popleft())

    def _settle(self, wave: tuple[Future, np.ndarray]) -> None:
        """Wait for a wave's puts, then free its buffer (even if they failed)."""
        fut, buf = wave
        try:
            fut.result()
        finally:
            self._wave_bufs.give(buf)

    def _put_wave(self, entries: list[tuple[_Group, np.ndarray]], step: int) -> None:
        """Land one wave's chunks on the fabric (runs on the I/O pool)."""
        stored = 0.0
        with telemetry.span("ckpt.put", request=step) as sp:
            for g, chunks in entries:
                for row, node in enumerate(g.node_ids):
                    self.fabric.put(node, f"{g.key}_r{row}", chunks[row].tobytes())
                    stored += chunks.shape[1]
            sp.nbytes = int(stored)
        with self._meta_lock:
            self.stats["bytes_stored"] += stored

    def save_async(self, state: PyTree, step: int) -> Future:
        # device_get on the caller thread (consistent snapshot), encode+put
        # in the background — the async checkpointing pattern of [29, 30].
        leaves, _ = jax.tree.flatten(state)
        host_leaves = [
            None if l is None else np.asarray(jax.device_get(l)) for l in leaves
        ]

        def work():
            fake_state = jax.tree.unflatten(jax.tree.structure(state), host_leaves)
            return self.save(fake_state, step)

        return self._save_pool.submit(work)

    # -- restore ----------------------------------------------------------------

    def restore_latest(self, like_state: PyTree) -> Optional[tuple[PyTree, int]]:
        if not self._manifests:
            return None
        step = max(self._manifests)
        return self.restore(step, like_state), step

    def restore(self, step: int, like_state: PyTree) -> PyTree:
        """Rebuild the state pytree. ``like_state`` provides the tree
        structure (a pytree of the saved structure — e.g. made with
        `jax.eval_shape` or real arrays)."""
        manifest = self._manifests[step]
        leaves_meta = manifest["leaves"]
        like_leaves, treedef = jax.tree.flatten(like_state)
        assert len(like_leaves) == len(
            [m for m in leaves_meta]
        ), "state structure mismatch"
        out_leaves = []
        for meta in leaves_meta:
            if meta is None:
                out_leaves.append(None)
                continue
            buf = io.BytesIO()
            # All groups of a leaf decode in cohort launches (per (K, P)
            # and erasure pattern) instead of one kernel call per group.
            for raw in self._load_groups([_Group(**g) for g in meta["groups"]]):
                buf.write(raw)
            arr = np.frombuffer(buf.getvalue(), dtype=np.dtype(meta["dtype"]))
            out_leaves.append(arr.reshape(meta["shape"]))
        return jax.tree.unflatten(treedef, out_leaves)

    def _load_groups(self, groups: list[_Group]) -> list[bytes]:
        """Fetch + decode many groups, batching decodes by (K, P)."""
        gathered: list[tuple[np.ndarray, np.ndarray, int]] = []
        for g in groups:
            rows, chunks = [], []
            for row, node in enumerate(g.node_ids):
                blob = self.fabric.get(node, f"{g.key}_r{row}")
                if blob is not None:
                    rows.append(row)
                    chunks.append(np.frombuffer(blob, dtype=np.uint8))
                if len(rows) == g.k:
                    break
            if len(rows) < g.k:
                raise IOError(
                    f"checkpoint group {g.key} unrecoverable: "
                    f"{len(rows)}/{g.k} chunks available (P={g.p} exceeded)"
                )
            gathered.append((np.stack(chunks), np.array(rows), g.orig_nbytes))
        outs: list = [None] * len(groups)
        for (k, p), idxs in plan_cohorts([(g.k, g.p) for g in groups]):
            codec = ECCodec(k, p, use_kernel=self.policy.use_kernel)
            for i, raw in zip(idxs, codec.decode_many([gathered[i] for i in idxs])):
                outs[i] = raw
        return outs

    def _load_group(self, g: _Group) -> bytes:
        return self._load_groups([g])[0]

    # -- failure handling ---------------------------------------------------------

    def on_node_failure(self, node_id: int) -> None:
        self.fabric.fail_node(node_id)

    def repair(self, step: Optional[int] = None, *, strict: bool = True) -> int:
        """Proactive repair: re-encode any group that lost chunks and place
        the replacements through ``PlacementEngine.plan_repair`` (keeps
        (K,P), re-maps; best-effort mode — group health is reported by
        :meth:`group_reliability`).  Returns the number of chunks rebuilt.

        Re-encodes run through the same cached-matrix cohort path as
        ``save`` (one launch per (K, P) cohort of degraded groups); the
        coding matrices themselves come from the process-wide cache, so
        steady-state repair rebuilds no matrices at all.

        A group whose missing chunks cannot *all* be re-placed (not enough
        live nodes with capacity) is left untouched and reported: with
        ``strict=True`` (default) an :class:`IOError` lists every such
        group after the repairable ones were fixed.  The old code silently
        under-repaired here — ``zip(missing, live)`` truncated when live
        candidates ran out, leaving groups degraded with no error.
        """
        step = step if step is not None else max(self._manifests)
        manifest = self._manifests[step]
        rebuilt = 0
        unplaced: list[tuple[str, int, str]] = []
        # 1. Collect every degraded group (reads only; no mutation yet).
        degraded: list[tuple[dict, _Group, list[tuple[int, int]]]] = []
        for meta in manifest["leaves"]:
            if meta is None:
                continue
            for gd in meta["groups"]:
                g = _Group(**gd)
                missing = [
                    (row, node)
                    for row, node in enumerate(g.node_ids)
                    if self.fabric.get(node, f"{g.key}_r{row}") is None
                ]
                if missing:
                    degraded.append((gd, g, missing))
        if not degraded:
            return 0
        # 2. Cohort re-encode: decode the survivors (raises if > P lost),
        # re-pad exactly as the original encode did (replacement chunks
        # must match the surviving chunks' shape), one launch per (K, P).
        payloads = self._load_groups([g for _, g, _ in degraded])
        specs = [(g.k, g.p) for _, g, _ in degraded]
        all_chunks: list = [None] * len(degraded)
        for (k, p), idxs in plan_cohorts(specs):
            codec = ECCodec(k, p, use_kernel=self.policy.use_kernel)
            for i, chunks in zip(
                idxs,
                codec.encode_many([_pad_to_bucket(payloads[i]) for i in idxs]),
            ):
                all_chunks[i] = chunks
        # 3. Re-place + land replacements, group by group (plans see the
        # fabric bytes earlier repairs already landed).
        for (gd, g, missing), chunks in zip(degraded, all_chunks):
            chunk_mb = chunks.shape[1] / 1e6
            missing_rows = {row for row, _ in missing}
            survivors = [
                node
                for row, node in enumerate(g.node_ids)
                if row not in missing_rows
            ]
            with self._place_lock:
                self._item_counter += 1
                item = DataItem(
                    item_id=self._item_counter,
                    size_mb=chunk_mb * g.k,
                    arrival_time=float(step),
                    delta_t_days=self.policy.retention_days,
                    reliability_target=self.policy.reliability_target,
                )
                # require_target=False: the code is fixed at (K, P), so
                # repair is best-effort re-mapping (no reliability DP to
                # amortize — group health is group_reliability()'s job);
                # commit=False because the fabric accounts bytes as
                # chunks land (fabric.put).
                plan = self.engine.plan_repair(
                    item,
                    Placement(k=g.k, p=g.p, node_ids=tuple(g.node_ids)),
                    chunk_mb=chunk_mb,
                    survivors=survivors,
                    allow_parity_growth=False,
                    require_target=False,
                    commit=False,
                )
            if not plan.ok:
                unplaced.append((g.key, len(missing), plan.reason))
                continue
            for (row, _), new_node in zip(missing, plan.new_nodes):
                self.fabric.put(new_node, f"{g.key}_r{row}", chunks[row].tobytes())
                g.node_ids[row] = new_node
                rebuilt += 1
            gd["node_ids"] = g.node_ids
        if unplaced and strict:
            detail = "; ".join(
                f"{key}: {n} missing chunk(s) ({reason})"
                for key, n, reason in unplaced
            )
            raise IOError(
                f"repair left {len(unplaced)} group(s) degraded: {detail}"
            )
        return rebuilt

    def group_reliability(self, step: Optional[int] = None) -> list[float]:
        """Current Pr_avail of every group (post-failure health metric)."""
        step = step if step is not None else max(self._manifests)
        out = []
        for meta in self._manifests[step]["leaves"]:
            if meta is None:
                continue
            for gd in meta["groups"]:
                alive = [n for n in gd["node_ids"] if self.fabric.cluster.alive[n]]
                lost = len(gd["node_ids"]) - len(alive)
                if lost > gd["p"]:
                    out.append(0.0)
                    continue
                fp = self.fabric.cluster.fail_probs(self.policy.retention_days)[alive]
                from repro.core.reliability import poisson_binomial_cdf

                out.append(poisson_binomial_cdf(fp, gd["p"] - lost))
        return out

    # -- gc -------------------------------------------------------------------------

    def _gc(self, newest_step: int) -> None:
        with self._meta_lock:
            steps = sorted(self._manifests)
            victims = []
            while len(steps) > self.policy.keep_last:
                victim = steps.pop(0)
                victims.append(self._manifests.pop(victim))
        for man in victims:
            for meta in man["leaves"]:
                if meta is None:
                    continue
                for gd in meta["groups"]:
                    for row, node in enumerate(gd["node_ids"]):
                        self.fabric.delete(node, f"{gd['key']}_r{row}")
