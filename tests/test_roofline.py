"""Roofline analyzer tests: jaxpr FLOP walker (scan/remat aware) and
post-SPMD HLO byte/collective analysis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import analyze_hlo, count_fn_flops
from repro.roofline.terms import RooflineTerms


class TestJaxprFlops:
    def test_plain_matmul(self):
        a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
        fc = count_fn_flops(lambda x, y: x @ y, a, b)
        assert fc.dot_flops == 2 * 64 * 128 * 32

    def test_scan_multiplies_by_length(self):
        x = jax.ShapeDtypeStruct((16, 32), jnp.float32)
        ws = jax.ShapeDtypeStruct((7, 32, 32), jnp.float32)

        def f(x, ws):
            return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]

        fc = count_fn_flops(f, x, ws)
        assert fc.dot_flops == 7 * 2 * 16 * 32 * 32

    def test_scanned_equals_unrolled(self):
        x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
        ws = jax.ShapeDtypeStruct((5, 16, 16), jnp.float32)

        def scanned(x, ws):
            return jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)[0].sum()

        def unrolled(x, ws):
            for i in range(5):
                x = jnp.tanh(x @ ws[i])
            return x.sum()

        a = count_fn_flops(scanned, x, ws)
        b = count_fn_flops(unrolled, x, ws)
        assert a.dot_flops == b.dot_flops

    def test_grad_includes_backward(self):
        x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
        w = jax.ShapeDtypeStruct((16, 16), jnp.float32)

        def loss(x, w):
            return (x @ w).sum()

        fwd = count_fn_flops(loss, x, w)
        bwd = count_fn_flops(jax.grad(loss, argnums=1), x, w)
        assert bwd.dot_flops >= fwd.dot_flops  # dgrad/wgrad dots

    def test_remat_recompute_counted(self):
        x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
        w = jax.ShapeDtypeStruct((16, 16), jnp.float32)

        def body(x, w):
            return jnp.tanh(x @ w)

        def loss_plain(x, w):
            return body(x, w).sum()

        def loss_remat(x, w):
            return jax.checkpoint(body)(x, w).sum()

        plain = count_fn_flops(jax.grad(loss_plain, argnums=1), x, w)
        remat = count_fn_flops(jax.grad(loss_remat, argnums=1), x, w)
        assert remat.dot_flops >= plain.dot_flops

    def test_batched_dot_general(self):
        a = jax.ShapeDtypeStruct((4, 8, 16), jnp.float32)
        b = jax.ShapeDtypeStruct((4, 16, 8), jnp.float32)
        fc = count_fn_flops(lambda x, y: jnp.einsum("bij,bjk->bik", x, y), a, b)
        assert fc.dot_flops == 4 * 2 * 8 * 16 * 8


def _compile(fn, *args, mesh_axes=None, in_shardings=None):
    if in_shardings is None:
        return jax.jit(fn).lower(*args).compile()
    from repro.launch.mesh import make_mesh_compat

    mesh = make_mesh_compat((jax.device_count(),), ("x",))
    with mesh:
        return jax.jit(fn, in_shardings=in_shardings).lower(*args).compile()


class TestHloAnalysis:
    def test_dot_flops_and_memory(self):
        a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
        b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
        comp = _compile(lambda x, y: x @ y, a, b)
        st = analyze_hlo(comp.as_text())
        assert st.dot_flops == 2 * 128 * 256 * 64
        want_bytes = (128 * 256 + 256 * 64 + 128 * 64) * 4
        assert st.memory_bytes >= want_bytes * 0.9
        assert st.memory_bytes <= want_bytes * 3

    def test_while_trip_count_scaling(self):
        x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
        ws = jax.ShapeDtypeStruct((9, 32, 32), jnp.float32)

        def f(x, ws):
            return jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)[0]

        st = analyze_hlo(_compile(f, x, ws).as_text())
        assert st.dot_flops == pytest.approx(9 * 2 * 32 * 32 * 32, rel=0.01)

    def test_no_collectives_single_device(self):
        a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        st = analyze_hlo(_compile(lambda x: (x @ x).sum(), a).as_text())
        assert st.total_collective_bytes == 0
        assert st.n_collectives == 0

    def test_scan_sliced_weights_not_overcounted(self):
        """Stacked scan weights read per layer must cost ~the slice, not
        trips x the whole stack."""
        x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((50, 64, 64), jnp.float32)

        def f(x, ws):
            return jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)[0].sum()

        st = analyze_hlo(_compile(f, x, ws).as_text())
        stack_bytes = 50 * 64 * 64 * 4
        # naive counting would be ~50 trips x full stack = 50x stack_bytes
        assert st.memory_bytes < 10 * stack_bytes


class TestRooflineTerms:
    def _terms(self, **kw):
        base = dict(
            arch="a", shape="s", mesh="single", chips=256,
            global_flops=1e15, per_device_hbm_bytes=1e11,
            per_device_collective_bytes=1e9, collective_breakdown={},
            model_flops=8e14,
        )
        base.update(kw)
        return RooflineTerms(**base)

    def test_terms_math(self):
        t = self._terms()
        assert t.compute_s == pytest.approx(1e15 / (256 * 197e12))
        assert t.memory_s == pytest.approx(1e11 / 819e9)
        assert t.collective_s == pytest.approx(1e9 / 50e9)
        assert t.bottleneck == "memory"

    def test_roofline_fraction_uses_useful_flops(self):
        t = self._terms()
        frac = t.roofline_fraction
        assert 0 < frac < 1
        # achieving the dominant term exactly with model flops:
        assert frac == pytest.approx(
            (8e14 / t.step_time_s) / (256 * 197e12)
        )

    def test_bottleneck_switches(self):
        t = self._terms(per_device_hbm_bytes=1.0, per_device_collective_bytes=1e13)
        assert t.bottleneck == "collective"
