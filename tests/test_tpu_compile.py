"""Compile the main path's kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jax, and it compiles for a chip that
is described rather than attached.  These tests lower the Pallas coding
kernel and the float64 decision kernels at the shapes the storage path
uses and assert that the v5e compiler accepts them; nothing runs, so
they say nothing about results or speed.  The decision kernels run on
the host CPU device (``repro.core.shapes.DECISION_PLATFORM``: the TPU's
emulated float64 does not round as numpy does); compiling them for the
v5e keeps them ready to move back once that is settled.

Shapes kept (each compiles within about 15 s on a CPU host):

* Pallas Cauchy-RS bit-matmul at (K, P) = (6, 2), (10, 4), (128, 16)
  over a 128 KiB byte column;
* D-Rex SC at the paper's 10-node pad (S, L) = (15, 16) and the largest
  exact-multiple rung (63, 64);
* D-Rex LB at 16, 136 and 280 nodes (280 = the 10k-node pre-filter
  slice);
* GreedyLeastUsed at 16 nodes and at its 32-node scan slice (batch 8);
* GreedyMinStorage at 16 and 136 nodes.

Left out: SC at L_pad >= 136 and GreedyLeastUsed at L_pad >= 136.  Their
float64 prefix-sum DP takes minutes to compile for the TPU
(GreedyLeastUsed at 136 about 4 minutes, SC's 1096-node pre-filter
slice about 3), so they stay out of the suite.

The topology is described inside a module-scoped fixture, never at
import: only the test worker that runs this file loads the TPU library.
The persistent compilation cache is off while these run, since a
program compiled for a described chip cannot be read back without one.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import greedy_kernel, lb_kernel, sc_kernel
from repro.core.reliability import _AUTO_EXACT_LIMIT
from repro.core.algorithms import DRexSC
from repro.kernels.rs_bitmatmul import gf_bitmatmul

F64, I64 = jnp.float64, jnp.int64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("k,p", [(6, 2), (10, 4), (128, 16)])
def test_pallas_coding_kernel(one_chip, k, p):
    compiled = jax.jit(gf_bitmatmul).lower(
        _spec(one_chip, (8 * p, 8 * k), jnp.float32),
        _spec(one_chip, (k, 128 * 1024), jnp.uint8),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("s_pad,l_pad", [(15, 16), (63, 64)])
def test_sc_kernel(one_chip, s_pad, l_pad):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    with jax.enable_x64(True):
        sc_kernel._score_windows.lower(
            s_pad, l_pad, DRexSC.MAX_MAPPINGS,
            s((1, l_pad), F64), *[s((1,), F64)] * 5, *[s((l_pad,), F64)] * 5,
            s((), I64), s((), F64), s((), F64), s((6,), F64),
        ).compile()


@pytest.mark.parametrize("l_pad", [16, 136, 280])
def test_lb_kernel(one_chip, l_pad):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    with jax.enable_x64(True):
        lb_kernel._lb_scores.lower(
            l_pad, s((1, l_pad), I64), s((1,), F64), s((l_pad,), F64),
            s((l_pad + 1,), F64), s((), F64), s((), I64),
        ).compile()


@pytest.mark.parametrize("batch,l_pad", [(1, 16), (8, 32)])
def test_least_used_kernel(one_chip, batch, l_pad):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    with jax.enable_x64(True):
        greedy_kernel._least_used_scores.lower(
            l_pad, s((batch, l_pad), F64), s((batch,), F64), s((batch,), F64),
            s((l_pad,), F64), s((), I64),
        ).compile()


@pytest.mark.parametrize("l_pad", [16, 136])
def test_min_storage_kernel(one_chip, l_pad):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    with jax.enable_x64(True):
        greedy_kernel._min_storage_scores.lower(
            l_pad, int(_AUTO_EXACT_LIMIT), s((1, l_pad), F64), s((1,), F64),
            s((1,), F64), s((1, l_pad + 1), I64), s((l_pad,), F64), s((), I64),
        ).compile()
