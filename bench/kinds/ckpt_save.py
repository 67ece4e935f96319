"""Kind ``ckpt_save``: checkpoint saves back to back.

Each save makes the state for its step on the chip and hands it to
``DRexCheckpointer.save`` (device-to-host copy, one ``place_many`` for
every group, Reed-Solomon encode on the chip, fabric puts).  The window
closes at the end of the first save that ends at or after ``--seconds``;
``save_MBps`` is the user bytes of every save in it over its length.

After the window every group of every save is compared: its (K, P,
nodes) with the reference D-Rex SC on the fleet as it stood when the
save began, its data rows with the state made again from the seed, and
its parity rows with the reference code (whole for groups drawn from
the seed, a drawn column slice for the others).
"""

from __future__ import annotations

import time

import numpy as np

import generate
import harness
import work
from reference import drex_sc, rs

#: parity columns compared per group, unless the group is compared whole.
PARITY_SAMPLE_BYTES = 1 << 16
#: groups of a window whose parity is compared whole, drawn from the seed.
FULL_PARITY_GROUPS = 6


class Runner(harness.Checkpoint):
    """Checkpoint saves back to back; ``save_MBps`` over the window."""

    def setup(self) -> None:
        self.build()
        self.step = 0
        self.saves = []
        for _ in range(int(self.mix["warm_saves"])):
            self._save()
        self.warm_saves = self.step

    def _save(self) -> dict:
        with self.spans.span("bench.make_state"):
            state = self.maker(self.step)
        cl = self.fabric.cluster
        rec = {"step": self.step, "used0": cl.used_mb.copy(), "alive0": cl.alive.copy()}
        with self.spans.span("bench.save"):
            try:
                rec["manifest"] = self.ck.save(state, self.step)
            except Exception as e:  # a save that fails has no answer to compare
                rec["error"] = repr(e)
        del state
        if "manifest" in rec:
            rec["blobs"] = [
                [self.fabric.get(node, f"{g['key']}_r{row}") for row, node in enumerate(g["node_ids"])]
                for g in work.groups(rec["manifest"])
            ]
        self.step += 1
        return rec

    def measure(self, seconds: float) -> dict:
        st0 = dict(self.ck.stats)
        t0 = time.perf_counter()
        while True:
            self.saves.append(self._save())
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        ok = [r for r in self.saves if "manifest" in r]
        saved = len(ok) * self.maker.nbytes
        st = self.ck.stats
        return {
            "elapsed_s": elapsed,
            "attempted": len(self.saves),
            "failed": len(self.saves) - len(ok),
            "end_to_end": {"save_MBps": saved / 1e6 / elapsed},
            "counters": {
                "bytes_saved": saved,
                "ckpt.encode_s": st["encode_s"] - st0["encode_s"],
                "ckpt.place_s": st["place_s"] - st0["place_s"],
            },
            "work": {"encode_bytes": sum(work.save_encode_bytes(r["manifest"]) for r in ok)},
        }

    def verify(self, control: bool = False) -> dict:
        sched = self.config["scheduler"]
        pol = self.mix["policy"]
        sizes = self.item_sizes()
        smin = min(sizes) if self.warm_saves else None
        rng = generate.rng_for(self.seed, 3)
        n_groups = len(self.saves) * len(self.parts)
        full = set(rng.choice(n_groups, size=min(FULL_PARITY_GROUPS, n_groups),
                              replace=False).tolist())
        off = {"saves_failed": 0, "decisions_off": 0, "chunks_missing": 0,
               "data_bytes_off": 0, "parity_bytes_off": 0}
        prev = self.host_leaves(self.saves[0]["step"] - 1) if control else None
        for si, rec in enumerate(self.saves):
            if "manifest" not in rec:
                off["saves_failed"] += 1
                continue
            groups = work.groups(rec["manifest"])
            cl64 = harness.ref_cluster(self.arr, rec["used0"], rec["alive0"])
            cl32 = harness.ref_cluster(self.arr, rec["used0"], rec["alive0"], np.float32)
            memo: dict = {}
            running = smin
            for g, size in zip(groups, sizes):
                running = size if running is None else min(running, size)
                key = (size, running)
                if key not in memo:
                    args = (size, pol["reliability_target"], pol["retention_days"], running, sched)
                    memo[key] = (drex_sc.decide(cl64, *args),
                                 drex_sc.decide(cl32, *args) if control else None)
                want, ctl = memo[key]
                got = ctl if control else (g["k"], g["p"], tuple(g["node_ids"]))
                off["decisions_off"] += got != want
            smin = running
            leaves = self.host_leaves(rec["step"])
            for gi, (g, blobs) in enumerate(zip(groups, rec["blobs"])):
                k, p = g["k"], g["p"]
                data = self.data_rows(leaves, gi, k)
                off["chunks_missing"] += sum(b is None for b in blobs)
                for row in range(k):
                    off["data_bytes_off"] += harness.count_off(blobs[row], data[row])
                cols = slice(None)
                if si * len(self.parts) + gi not in full:
                    start = int(rng.integers(0, max(1, data.shape[1] - PARITY_SAMPLE_BYTES + 1)))
                    cols = slice(start, start + PARITY_SAMPLE_BYTES)
                want = rs.encode(data[:, cols], p)
                if control:
                    got_par = rs.encode(self.data_rows(prev, gi, k)[:, cols], p)
                else:
                    got_par = [None if blobs[k + r] is None
                               else np.frombuffer(blobs[k + r], dtype=np.uint8)[cols]
                               for r in range(p)]
                for r in range(p):
                    off["parity_bytes_off"] += harness.count_off(got_par[r], want[r])
            prev = leaves
        return off
