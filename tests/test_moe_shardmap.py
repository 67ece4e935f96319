"""Equivalence of the shard_map MoE dispatch vs the global-view scatter
path (the §Perf iteration-11 optimization must not change the math)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh_compat
from repro.models import forward, init_params
from repro.models.sharding import activate_mesh

# shard_map dispatch equivalence sweeps: full lane only (deselect via -m "not slow").
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3_moe_30b_a3b", smoke=True)
    # generous capacity so local-vs-global queue semantics coincide
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0),
                    moe_dispatch="scatter")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    return cfg, params, toks


def _mesh():
    return make_mesh_compat((1, 1), ("data", "model"))


class TestShardMapDispatch:
    def test_forward_bit_exact(self, setup):
        cfg, params, toks = setup
        ref, _ = forward(params, toks, cfg)
        mesh = _mesh()
        cfg_sm = cfg.with_(moe_dispatch="shard_map")
        with activate_mesh(mesh), mesh:
            got, _ = jax.jit(lambda p, t: forward(p, t, cfg_sm))(params, toks)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_gradients_bit_exact(self, setup):
        cfg, params, toks = setup
        g_ref = jax.grad(lambda p: (forward(p, toks, cfg)[0] ** 2).mean())(params)
        mesh = _mesh()
        cfg_sm = cfg.with_(moe_dispatch="shard_map")
        with activate_mesh(mesh), mesh:
            g_sm = jax.jit(
                jax.grad(lambda p: (forward(p, toks, cfg_sm)[0] ** 2).mean())
            )(params)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_sm)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_falls_back_without_mesh(self, setup):
        """No active mesh -> scatter path (CPU tests, eager use)."""
        cfg, params, toks = setup
        cfg_sm = cfg.with_(moe_dispatch="shard_map")
        ref, _ = forward(params, toks, cfg)
        got, _ = forward(params, toks, cfg_sm)  # no activate_mesh
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_padded_experts_with_shardmap(self):
        """qwen2-moe config: padding + shard_map together."""
        cfg = get_config("qwen2_moe_a2_7b", smoke=True)
        cfg = cfg.with_(
            moe=dataclasses.replace(cfg.moe, pad_experts_to=12, capacity_factor=8.0)
        )
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
        ref, _ = forward(params, toks, cfg.with_(moe_dispatch="scatter"))
        mesh = _mesh()
        with activate_mesh(mesh), mesh:
            got, _ = jax.jit(
                lambda p, t: forward(p, t, cfg.with_(moe_dispatch="shard_map"))
            )(params, toks)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
