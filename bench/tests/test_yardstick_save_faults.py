"""The save cell at a toy size on the CPU: a sound run is correct; the
control and each planted fault make ``correct`` false."""

import numpy as np
import pytest

from yardstick_tiny import run_tiny

CELLS = pytest.mark.parametrize("cell", ["ckpt_save.chameleon"])


@CELLS
def test_sound_run_is_correct(monkeypatch, cell):
    out = run_tiny(monkeypatch, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@CELLS
def test_control_is_not_correct(monkeypatch, cell):
    out = run_tiny(monkeypatch, cell, control=True)
    assert not out["correct"]
    assert out["checks"]["parity_bytes_off"]["value"] > 0
    # Float32 moves a decision on the ten nodes.
    assert out["checks"]["decisions_off"]["value"] > 0


@CELLS
def test_parity_altered_where_produced(monkeypatch, cell):
    from repro.kernels import ops

    real = ops.encode_chunks_many

    def flipped(*a, **kw):
        outs = [np.array(o) for o in real(*a, **kw)]
        outs[0][0, 0] ^= 1
        return outs

    monkeypatch.setattr(ops, "encode_chunks_many", flipped)
    out = run_tiny(monkeypatch, cell)
    assert not out["correct"] and out["checks"]["parity_bytes_off"]["value"] > 0


@CELLS
def test_half_of_the_groups_never_land(monkeypatch, cell):
    from repro.checkpoint import StorageFabric

    real = StorageFabric.put

    def half(self, node, key, blob):
        leaf = int(key.split("_l")[1].split("_")[0])
        if leaf % 2:  # every other group of a save is dropped whole
            return None
        return real(self, node, key, blob)

    monkeypatch.setattr(StorageFabric, "put", half)
    out = run_tiny(monkeypatch, cell)
    assert not out["correct"] and out["checks"]["chunks_missing"]["value"] > 0


@CELLS
def test_save_that_leaves_the_fabric_unchanged(monkeypatch, cell):
    from repro.checkpoint import StorageFabric

    real = StorageFabric.put
    monkeypatch.setattr(StorageFabric, "put",
                        lambda self, node, key, blob: None if key.startswith("ck2") else real(self, node, key, blob))
    out = run_tiny(monkeypatch, cell, seconds=0.5)
    assert not out["correct"] and out["checks"]["chunks_missing"]["value"] > 0


@CELLS
def test_decision_altered_where_produced(monkeypatch, cell):
    from repro.core.algorithms import DRexSC

    real = DRexSC.place_batch

    def moved(self, items, cluster, ctx=None, constraints=None):
        out = real(self, items, cluster, ctx, constraints)
        d = out[0]
        if d.placement is not None:
            ids = d.placement.node_ids
            spare = next(n for n in cluster.live_ids() if n not in ids)
            pl = type(d.placement)(k=d.placement.k, p=d.placement.p,
                                   node_ids=(int(spare),) + tuple(ids[1:]))
            out[0] = type(d)(pl, d.candidates_considered, d.reason)
        return out

    monkeypatch.setattr(DRexSC, "place_batch", moved)
    out = run_tiny(monkeypatch, cell)
    assert not out["correct"] and out["checks"]["decisions_off"]["value"] > 0
