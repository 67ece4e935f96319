"""The seeded generators: the same seed gives the same inputs."""

import numpy as np
import pytest

from yardstick_tiny import BENCH, TINY_STATE, run

import generate  # noqa: E402


def _load(folder, name):
    return run.load_json(BENCH, folder, f"{name}.json")


def test_yi6b_shard_size():
    config = _load("configs", "chameleon")
    leaves = generate.shard_leaves(config)
    assert config["num_hidden_layers"] == 3
    assert len(leaves) == 81
    assert 4 * sum(n for _, n in leaves) == 778_604_544
    per_layer_params = sum(n for _, n in leaves) // 3 // 3
    assert per_layer_params == 21_627_904


def test_state_same_seed_same_leaves_other_seed_differs():
    state = dict(_load("configs", "chameleon"), **TINY_STATE)
    scales = _load("traffic", "ckpt_save")["scales"]
    a = generate.StateMaker(state, scales, 2**33 + 7)
    b = generate.StateMaker(state, scales, 2**33 + 7)
    c = generate.StateMaker(state, scales, 2**33 + 8)
    for x, y in zip(a(4), b(4)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a(4)[0]), np.asarray(c(4)[0]))
    assert not np.array_equal(np.asarray(a(4)[0]), np.asarray(a(5)[0]))


@pytest.mark.parametrize("config", ["chameleon"])
def test_cluster_deterministic(config):
    """Table 5's ten nodes, empty, the same on every call."""
    cl = _load("configs", config)["cluster"]
    a, b = generate.cluster_arrays(cl), generate.cluster_arrays(cl)
    assert len(a["capacity_mb"]) == len(cl["rows"]) == 10
    for key in ("capacity_mb", "used_mb", "write_bw", "read_bw", "afr"):
        assert np.array_equal(a[key], b[key])
    assert not a["used_mb"].any()
    assert a["capacity_mb"].sum() == pytest.approx(1e6 * sum(row[1] for row in cl["rows"]))


def test_unknown_cluster_kind_is_refused():
    with pytest.raises(ValueError, match="cluster kind"):
        generate.cluster_arrays({"kind": "fleet", "rows": []})
