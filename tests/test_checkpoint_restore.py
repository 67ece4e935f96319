"""Checkpoint restore: bit-exact round trips of every dtype and shape a
state holds, restores after node losses under every scheduler and node
set, the error past P losses, the stored layout a restore reads, and the
pytree structures a state comes in."""

import math
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _train_state import states_equal, tiny_state
from repro.checkpoint import CheckpointPolicy, DRexCheckpointer, StorageFabric
from repro.storage import make_node_set
from repro.storage.nodesets import NODE_SETS

# checkpoint save/restore e2e: full lane only (deselect via -m "not slow").
pytestmark = pytest.mark.slow

SCHEDULERS = ["drex_sc", "drex_lb", "greedy_min_storage", "greedy_least_used"]

#: a job that keeps its checkpoints for a year at five nines: every
#: scheduler buys P >= 2 on three of the four node sets (P = 1 on
#: ``most_reliable``), and at this capacity scale every pair places
#: every group of ``tiny_state``.
POLICY = CheckpointPolicy(item_mb=0.25, reliability_target=0.99999, retention_days=365.0)
CAPACITY_SCALE = 1e-5


def checkpointer(scheduler="drex_sc", nodeset="most_used"):
    fabric = StorageFabric(make_node_set(nodeset, capacity_scale=CAPACITY_SCALE))
    return fabric, DRexCheckpointer(fabric, scheduler, POLICY)


def groups_of(manifest) -> list[dict]:
    return [g for meta in manifest["leaves"] if meta is not None for g in meta["groups"]]


def bucket(nbytes: int) -> int:
    """The power-of-two bucket, at least 4 KiB, a group is padded to."""
    return max(4096, 1 << max(nbytes - 1, 0).bit_length())


DTYPES = ["float32", "bfloat16", "float16", "int32", "int8", "uint8", "bool"]
SHAPES = [(), (0,), (1,), (4097,), (3, 70001)]


def random_array(dtype: str, shape: tuple, seed: int) -> np.ndarray:
    """Random bits of ``dtype``: every float bit pattern, NaNs included."""
    rng = np.random.default_rng(seed)
    dt = jnp.dtype(dtype)
    n = math.prod(shape)
    if dt == np.bool_:
        return rng.integers(0, 2, size=shape).astype(np.bool_)
    return rng.integers(0, 256, size=n * dt.itemsize, dtype=np.uint8).view(dt).reshape(shape)


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)) or "scalar")
@pytest.mark.parametrize("dtype", DTYPES)
def test_roundtrip_exact(dtype, shape):
    """A leaf comes back with its dtype, its shape and its bits.  At
    ``item_mb=0.25`` the (3, 70001) leaf of a 2- or 4-byte dtype spans
    several groups and ends in a short tail."""
    host = random_array(dtype, shape, seed=DTYPES.index(dtype) * 10 + SHAPES.index(shape))
    _, ck = checkpointer()
    manifest = ck.save({"x": jnp.asarray(host)}, 1)
    max_bytes = int(POLICY.item_mb * 1e6)
    assert len(groups_of(manifest)) == max(1, -(-host.nbytes // max_bytes))
    restored = ck.restore(1, {"x": host})["x"]
    assert restored.dtype == host.dtype
    assert restored.shape == host.shape
    assert restored.tobytes() == host.tobytes()


def most_loaded_node(manifest) -> int:
    """The node holding rows of the most groups (lowest id on ties)."""
    counts = np.zeros(max(n for g in groups_of(manifest) for n in g["node_ids"]) + 1, int)
    for g in groups_of(manifest):
        counts[g["node_ids"]] += 1
    return int(np.argmax(counts))


@pytest.mark.parametrize("lost", ["one_node", "min_p"])
@pytest.mark.parametrize("nodeset", list(NODE_SETS))
@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_restore_after_losses(scheduler, nodeset, lost):
    """Restore is bit-exact after losing the node that holds the most
    groups' rows, or the data rows' nodes of the group with the smallest
    P, P of them (so decoding, not the systematic rows, rebuilds it)."""
    state = tiny_state()
    fabric, ck = checkpointer(scheduler, nodeset)
    manifest = ck.save(state, 1)
    groups = groups_of(manifest)
    if lost == "one_node":
        victims = [most_loaded_node(manifest)]
    else:
        g = min(groups, key=lambda g: g["p"])
        victims = g["node_ids"][: g["p"]]
    for n in victims:
        fabric.fail_node(n)
    assert all(sum(n in victims for n in g["node_ids"]) <= g["p"] for g in groups)
    restored, step = ck.restore_latest(state)
    assert step == 1
    assert states_equal(state, restored)


@pytest.mark.parametrize("nodeset", list(NODE_SETS))
@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_more_than_p_lost_raises(scheduler, nodeset):
    """Losing P+1 nodes of a group makes it unrecoverable, and the
    error names that group."""
    state = tiny_state()
    fabric, ck = checkpointer(scheduler, nodeset)
    g = groups_of(ck.save(state, 1))[0]
    for n in g["node_ids"][: g["p"] + 1]:
        fabric.fail_node(n)
    with pytest.raises(IOError, match=rf"checkpoint group {re.escape(g['key'])} unrecoverable"):
        ck.restore(1, state)


@pytest.mark.parametrize("nodeset", list(NODE_SETS))
@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_stored_layout(scheduler, nodeset):
    """What a restore reads: each group's N rows on the N distinct nodes
    its manifest names, each ceil(bucket / K) bytes long, and nothing
    else on the fabric."""
    fabric, ck = checkpointer(scheduler, nodeset)
    groups = groups_of(ck.save(tiny_state(), 1))
    n_rows = 0
    for g in groups:
        n = g["k"] + g["p"]
        assert len(g["node_ids"]) == n
        assert len(set(g["node_ids"])) == n
        want = -(-bucket(g["orig_nbytes"]) // g["k"])
        for row, node in enumerate(g["node_ids"]):
            blob = fabric.get(node, f"{g['key']}_r{row}")
            assert blob is not None and len(blob) == want
        n_rows += n
    blobs = [b for node in fabric._blobs for b in node.values()]
    assert len(blobs) == n_rows
    assert sum(map(len, blobs)) == ck.stats["bytes_stored"]


class Opt(NamedTuple):
    m: jax.Array
    v: jax.Array
    step: jax.Array


def _leaf(i: int, shape=(33, 17)) -> jax.Array:
    return jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)


STRUCTURES = {
    "dict": lambda: {"w": _leaf(0), "b": _leaf(1, (17,))},
    "nested": lambda: {
        "params": {"layers": [{"w": _leaf(2)}, {"w": _leaf(3)}], "head": _leaf(4)},
        "history": [_leaf(5, (4,)), [_leaf(6, (2, 2))]],
    },
    "tuple": lambda: (_leaf(7), _leaf(8, ()), _leaf(9, (5,))),
    "namedtuple": lambda: Opt(m=_leaf(10), v=_leaf(11), step=jnp.int32(7)),
    "none_leaves": lambda: {"w": _leaf(12), "ema": None, "opt": (_leaf(13), None)},
}


@pytest.mark.parametrize("kind", list(STRUCTURES))
def test_pytree_structures(kind):
    """A restore into the structure of ``jax.eval_shape`` of the state
    (no arrays needed) gives back the state's own structure."""
    state = STRUCTURES[kind]()
    _, ck = checkpointer()
    ck.save(state, 1)
    restored = ck.restore(1, jax.eval_shape(lambda: state))
    assert jax.tree.structure(restored) == jax.tree.structure(state)
    assert type(restored) is type(state)
    assert states_equal(state, restored)
