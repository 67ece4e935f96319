"""Pallas kernel tests: bit-matmul vs pure-jnp oracle, shape/dtype sweeps,
roundtrip-with-erasures property tests (hypothesis)."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # dev-only dep (requirements-dev.txt); keep invariants running
    from _hypothesis_stub import given, settings, strategies as st

from repro.ec import ECCodec, gf256
from repro.kernels import ops, ref
from repro.kernels.rs_bitmatmul import gf_bitmatmul

# codec roundtrip property sweeps: full lane only (deselect via -m "not slow").
pytestmark = pytest.mark.slow


class TestGF256Host:
    def test_mul_identity_and_zero(self):
        a = np.arange(256, dtype=np.uint8)
        assert np.array_equal(gf256.gf_mul(a, 1), a)
        assert np.all(gf256.gf_mul(a, 0) == 0)

    def test_mul_commutative_associative(self):
        rng = np.random.default_rng(0)
        a, b, c = rng.integers(0, 256, size=(3, 1000), dtype=np.uint8)
        assert np.array_equal(gf256.gf_mul(a, b), gf256.gf_mul(b, a))
        assert np.array_equal(
            gf256.gf_mul(gf256.gf_mul(a, b), c), gf256.gf_mul(a, gf256.gf_mul(b, c))
        )

    def test_inverse(self):
        a = np.arange(1, 256, dtype=np.uint8)
        assert np.all(gf256.gf_mul(a, gf256.gf_inv(a)) == 1)

    def test_distributive_over_xor(self):
        rng = np.random.default_rng(1)
        a, b, c = rng.integers(0, 256, size=(3, 1000), dtype=np.uint8)
        assert np.array_equal(
            gf256.gf_mul(a, b ^ c), gf256.gf_mul(a, b) ^ gf256.gf_mul(a, c)
        )

    def test_matrix_inverse(self):
        rng = np.random.default_rng(2)
        for n in (2, 3, 5, 8):
            # Cauchy matrices are always invertible.
            m = gf256.cauchy_matrix(n, n)
            inv = gf256.gf_mat_inv(m)
            assert np.array_equal(gf256.gf_matmul(m, inv), np.eye(n, dtype=np.uint8))

    def test_singular_matrix_raises(self):
        m = np.array([[1, 2], [1, 2]], dtype=np.uint8)
        with pytest.raises(np.linalg.LinAlgError):
            gf256.gf_mat_inv(m)

    @pytest.mark.parametrize("k,p", [(2, 1), (3, 2), (4, 2), (6, 3), (8, 2), (16, 4)])
    def test_any_k_rows_of_generator_invertible(self, k, p):
        """The MDS property that makes K-of-N recovery work at all."""
        rng = np.random.default_rng(k * 100 + p)
        g = gf256.generator_matrix(k, p)
        for _ in range(10):
            rows = rng.choice(k + p, size=k, replace=False)
            gf256.gf_mat_inv(g[np.sort(rows)])  # must not raise


class TestBitmatrix:
    @pytest.mark.parametrize("r,k", [(1, 2), (2, 3), (2, 4), (3, 6), (4, 8), (4, 16)])
    def test_bitmatrix_equals_gf_matmul(self, r, k):
        rng = np.random.default_rng(r * 10 + k)
        m = rng.integers(0, 256, size=(r, k), dtype=np.uint8)
        data = rng.integers(0, 256, size=(k, 512), dtype=np.uint8)
        bm = gf256.gf_to_bitmatrix(m)
        got = np.asarray(ref.bitmatmul_ref(bm, data))
        want = gf256.gf_matmul(m, data)
        np.testing.assert_array_equal(got, want)

    def test_bitmatrix_shape_and_binary(self):
        m = gf256.cauchy_matrix(3, 5)
        bm = gf256.gf_to_bitmatrix(m)
        assert bm.shape == (24, 40)
        assert set(np.unique(bm)) <= {0, 1}


class TestPallasKernel:
    """``interpret=True`` runs the Pallas kernel in the interpreter on
    the CPU — the correctness gate for the kernel body itself.  Without
    it the CPU kernel path is the jitted XLA twin (see
    repro.kernels.ops), which TestXlaTwin covers."""

    @pytest.mark.parametrize(
        "k,p,nbytes",
        [
            (2, 1, 2048),
            (3, 2, 2048),
            (4, 2, 4096),
            (6, 3, 2048),
            (8, 2, 6144),
            (10, 4, 2048),
            (16, 4, 4096),
        ],
    )
    def test_encode_matches_oracle(self, k, p, nbytes):
        rng = np.random.default_rng(k * 1000 + p)
        data = rng.integers(0, 256, size=(k, nbytes), dtype=np.uint8)
        got = np.asarray(ops.encode_chunks(data, p, use_kernel=True, interpret=True))
        want = np.asarray(ops.encode_chunks(data, p, use_kernel=False))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("nbytes", [1, 7, 100, 2047, 2048, 2049, 10_000])
    def test_unaligned_sizes_padded_correctly(self, nbytes):
        rng = np.random.default_rng(nbytes)
        data = rng.integers(0, 256, size=(4, nbytes), dtype=np.uint8)
        got = np.asarray(ops.encode_chunks(data, 2, use_kernel=True, interpret=True))
        want = np.asarray(ops.encode_chunks(data, 2, use_kernel=False))
        assert got.shape == (2, nbytes)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("block", [256, 1024, 2048])
    def test_block_size_invariance(self, block):
        rng = np.random.default_rng(block)
        data = rng.integers(0, 256, size=(5, 4096), dtype=np.uint8)
        a = np.asarray(ops.encode_chunks(data, 3, block_bytes=block, interpret=True))
        b = np.asarray(ops.encode_chunks(data, 3, block_bytes=2048, interpret=True))
        np.testing.assert_array_equal(a, b)

    def test_decode_kernel_matches_oracle(self):
        rng = np.random.default_rng(5)
        k, p = 5, 3
        g = gf256.generator_matrix(k, p)
        data = rng.integers(0, 256, size=(k, 3000), dtype=np.uint8)
        all_chunks = gf256.gf_matmul(g, data)
        rows = np.array([0, 2, 5, 6, 7])  # mix of data+parity rows
        got = np.asarray(
            ops.decode_chunks(all_chunks[rows], rows, k, p,
                              use_kernel=True, interpret=True)
        )
        want = np.asarray(
            ops.decode_chunks(all_chunks[rows], rows, k, p, use_kernel=False)
        )
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got, data)


class TestXlaTwin:
    """The off-TPU kernel path (jitted, tiled XLA bit-matmul) must match
    the oracle too — it is what CPU CI times in benchmarks/fig1."""

    @pytest.mark.parametrize("k,p,nbytes", [(3, 2, 2048), (6, 3, 70_000)])
    def test_encode_matches_oracle(self, k, p, nbytes):
        rng = np.random.default_rng(k * 7 + nbytes)
        data = rng.integers(0, 256, size=(k, nbytes), dtype=np.uint8)
        got = np.asarray(ops.encode_chunks(data, p, use_kernel=True))
        want = np.asarray(ops.encode_chunks(data, p, use_kernel=False))
        np.testing.assert_array_equal(got, want)

    def test_tiled_width_equals_untiled(self):
        # wide enough that the lax.map tiling path runs (> EC_TILE_BLOCKS
        # blocks); narrow calls take the single-call branch.
        rng = np.random.default_rng(11)
        wide = rng.integers(
            0, 256, size=(4, ops.EC_TILE_BLOCKS * 2048 * 5), dtype=np.uint8
        )
        got = np.asarray(ops.encode_chunks(wide, 2, use_kernel=True))
        want = np.asarray(ops.encode_chunks(wide, 2, use_kernel=False))
        np.testing.assert_array_equal(got, want)

    def test_rejects_bad_shapes(self):
        import jax.numpy as jnp

        with pytest.raises(AssertionError):
            gf_bitmatmul(
                jnp.zeros((15, 16), jnp.float32), jnp.zeros((2, 2048), jnp.uint8)
            )


class TestCodecRoundtrip:
    @given(
        k=st.integers(2, 10),
        p=st.integers(1, 4),
        nbytes=st.integers(1, 40_000),
        seed=st.integers(0, 2**31),
    )
    @settings(max_examples=30, deadline=None)
    def test_property_roundtrip_any_k_surviving(self, k, p, nbytes, seed):
        rng = np.random.default_rng(seed)
        payload = rng.integers(0, 256, size=nbytes, dtype=np.uint8).tobytes()
        codec = ECCodec(k, p)
        chunks = codec.encode(payload)
        assert chunks.shape[0] == k + p
        keep = np.sort(rng.choice(k + p, size=k, replace=False))
        out = codec.decode(chunks[keep], keep, nbytes)
        assert out == payload

    def test_tolerates_exactly_p_failures_not_more(self):
        rng = np.random.default_rng(0)
        payload = rng.integers(0, 256, size=9999, dtype=np.uint8).tobytes()
        codec = ECCodec(4, 2)
        chunks = codec.encode(payload)
        keep = np.array([2, 3, 4, 5])  # lose rows 0,1 (= P failures): fine
        assert codec.decode(chunks[keep], keep, 9999) == payload
        with pytest.raises(ValueError):
            codec.decode(chunks[:3], np.arange(3), 9999)  # K-1 chunks

    def test_systematic_fast_path(self):
        payload = b"hello world" * 1000
        codec = ECCodec(3, 2)
        chunks = codec.encode(payload)
        rows = np.arange(3)
        assert codec.decode(chunks[:3], rows, len(payload)) == payload

    def test_empty_ish_payload(self):
        codec = ECCodec(4, 2)
        chunks = codec.encode(b"x")
        keep = np.array([0, 3, 4, 5])
        assert codec.decode(chunks[keep], keep, 1) == b"x"
