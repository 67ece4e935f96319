"""Shared pieces of the traffic kinds in ``bench/kinds``.

A kind builds the system under test from a configuration and a mix
(``setup``), runs it back to back for a measured window (``measure``)
and afterwards holds what the window produced against the plain
references under ``bench/reference`` (``verify``).  ``verify(control=
True)`` puts the control in the program's place instead: the reference
computed in float32 for placement decisions, and for the bytes a
reference that breaks the stated guarantee (the parity of the previous
checkpoint).  Every check is a count whose limit is 0: the comparisons
are exact.
"""

from __future__ import annotations

import numpy as np

import generate
import trace_reduce
from reference import drex_sc


def make_scheduler(spec: dict):
    """The configuration's scheduler, with its stated constants."""
    from repro.core import create_scheduler
    from repro.core.types import ECTimeModel

    sched = create_scheduler(spec["name"])
    sched.time_model = ECTimeModel(**spec["time_model"])
    sched.MAX_MAPPINGS = int(spec["max_mappings"])
    return sched


def count_off(got, want) -> int:
    """Bytes that differ; a missing or wrong-length answer counts whole."""
    want = np.asarray(want, dtype=np.uint8).reshape(-1)
    if got is None:
        return int(want.size)
    got = np.frombuffer(got, dtype=np.uint8) if isinstance(got, (bytes, bytearray)) \
        else np.asarray(got, dtype=np.uint8).reshape(-1)
    if got.size != want.size:
        return int(max(got.size, want.size))
    return int(np.count_nonzero(got != want))


def bucket(nbytes: int) -> int:
    """The checkpoint's group payload size: the next power of two from 4 KiB."""
    b = 4096
    while b < nbytes:
        b <<= 1
    return b


def ref_cluster(arr: dict, used, alive, dtype=np.float64) -> drex_sc.Cluster:
    cl = drex_sc.Cluster(arr["capacity_mb"], used, arr["write_bw"], arr["read_bw"],
                         arr["afr"], dtype=dtype)
    cl.alive = np.array(alive, dtype=bool)
    return cl


class Checkpoint:
    """Shared set-up of checkpoint mixes: the configuration's cluster
    behind a ``DRexCheckpointer``, and its training state cut into groups."""

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, seed
        self.spans = trace_reduce.SpanLog()
        self.arr = generate.cluster_arrays(config["cluster"])
        self.max_bytes = int(mix["policy"]["item_mb"] * 1e6)
        #: (leaf index, offset, length) of every group, in placement order.
        self.parts = []
        for li, (_name, n) in enumerate(generate.shard_leaves(config)):
            nbytes = 4 * n
            for off in range(0, max(nbytes, 1), self.max_bytes):
                self.parts.append((li, off, min(nbytes, off + self.max_bytes) - off))

    def build(self):
        from repro.checkpoint import CheckpointPolicy, DRexCheckpointer, StorageFabric
        from repro.core.types import StorageNode

        a = self.arr
        nodes = [
            StorageNode(node_id=i, name=a["names"][i], capacity_mb=float(a["capacity_mb"][i]),
                        write_bw=float(a["write_bw"][i]), read_bw=float(a["read_bw"][i]),
                        annual_failure_rate=float(a["afr"][i]), used_mb=float(a["used_mb"][i]),
                        rack=int(a["rack"][i]), zone=int(a["zone"][i]))
            for i in range(len(a["names"]))
        ]
        self.fabric = StorageFabric(nodes, link_mbps=self.config["fabric"]["link_mbps"])
        self.ck = DRexCheckpointer(
            self.fabric, make_scheduler(self.config["scheduler"]),
            CheckpointPolicy(**self.mix["policy"]),
        )
        self.maker = generate.StateMaker(self.config, self.mix["scales"], self.seed)

    def host_leaves(self, step: int) -> list[np.ndarray]:
        """The reference's copy of the state saved at ``step``, as bytes."""
        import jax

        return [np.asarray(x).view(np.uint8) for x in jax.device_get(self.maker(step))]

    def data_rows(self, leaves, gi: int, k: int) -> np.ndarray:
        """(K, B) data rows of group ``gi``: its bytes, zero-padded to the
        bucket and cut into K rows."""
        li, off, n = self.parts[gi]
        padded = bucket(n)
        b = -(-padded // k)
        out = np.zeros(k * b, dtype=np.uint8)
        out[:n] = leaves[li][off : off + n]
        return out.reshape(k, b)

    def item_sizes(self) -> list[float]:
        return [max(bucket(n) / 1e6, 1e-6) for _, _, n in self.parts]
