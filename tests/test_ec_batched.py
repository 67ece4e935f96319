"""Batched erasure-coding data plane: the multi-item launch paths
(``encode_chunks_many`` / ``decode_chunks_many`` and their codec
wrappers) pinned bit-for-bit against the per-item oracle, plus the
coding-matrix LRU cache and the compile census."""

import itertools

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # dev-only dep (requirements-dev.txt)
    from _hypothesis_stub import given, settings, strategies as st

from repro.core import shapes as core_shapes
from repro.ec import ECCodec, encode_batch, plan_cohorts
from repro.kernels import ops


def _payloads(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256, size=n, dtype=np.uint8).tobytes() for n in lengths
    ]


class TestEncodeMany:
    @given(
        k=st.integers(2, 8),
        p=st.integers(1, 4),
        lengths=st.lists(st.integers(0, 9000), min_size=1, max_size=8),
        seed=st.integers(0, 2**31),
        spare=st.sampled_from([None, 0, 100]),
    )
    @settings(max_examples=25, deadline=None)
    def test_property_matches_per_item(self, k, p, lengths, seed, spare):
        """One cohort launch is byte-identical to per-item encodes across
        mixed lengths, tail (non-bucket-aligned) widths and empties, in a
        fresh wave buffer (``spare`` None) or a caller's stale one with
        ``spare`` bytes to spare."""
        payloads = _payloads(lengths, seed)
        codec = ECCodec(k, p)
        out = None
        if spare is not None:
            out = np.full(codec.wave_nbytes([len(pl) for pl in payloads]) + spare, 0xA5, np.uint8)
        got = codec.encode_many(payloads, out=out)
        want = [codec.encode(pl) for pl in payloads]
        assert len(got) == len(want)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    @pytest.mark.parametrize("given_out", [False, True])
    def test_chunks_are_views_of_one_wave_buffer(self, given_out):
        codec = ECCodec(4, 2)
        payloads = _payloads([5000, 0, 4097, 12], seed=7)
        nbytes = codec.wave_nbytes([len(pl) for pl in payloads])
        out = np.empty(nbytes, np.uint8) if given_out else None
        got = codec.encode_many(payloads, out=out)
        base = out if given_out else got[0].base
        assert base.shape == (nbytes,)
        for chunks, pl in zip(got, payloads):
            assert chunks.shape == (6, codec.chunk_len(len(pl)))
            assert chunks.base is base
            assert all(row.flags.c_contiguous for row in chunks)

    def test_too_small_wave_buffer_raises(self):
        codec = ECCodec(4, 2)
        payloads = _payloads([5000, 300], seed=8)
        nbytes = codec.wave_nbytes([5000, 300])
        assert nbytes == 6 * (1250 + 75)
        for bad in (np.empty(nbytes - 1, np.uint8), np.empty(nbytes, np.int8),
                    np.empty((2, nbytes), np.uint8), np.empty(2 * nbytes, np.uint8)[::2]):
            with pytest.raises(ValueError, match="wave buffer"):
                codec.encode_many(payloads, out=bad)

    def test_mixed_kp_batch_matches_per_item(self):
        specs = [(3, 2), (6, 3), (3, 2), (4, 2), (6, 3)]
        payloads = _payloads([5000, 100, 0, 8192, 2048], seed=3)
        got = encode_batch(specs, payloads)
        for (k, p), pl, chunks in zip(specs, payloads, got):
            np.testing.assert_array_equal(chunks, ECCodec(k, p).encode(pl))

    def test_cohort_mixing_k_raises(self):
        with pytest.raises(ValueError, match="plan_cohorts"):
            ops.encode_chunks_many(
                [np.zeros((3, 8), np.uint8), np.zeros((4, 8), np.uint8)], 2
            )

    def test_empty_cohort(self):
        assert ops.encode_chunks_many([], 2) == []

    def test_pallas_interpret_matches(self):
        """The Pallas cohort launch, interpreted on the CPU, agrees."""
        datas = [
            np.random.default_rng(i).integers(0, 256, size=(4, 3000), dtype=np.uint8)
            for i in range(3)
        ]
        got = ops.encode_chunks_many(datas, 2, interpret=True)
        want = [np.asarray(ops.encode_chunks(d, 2, use_kernel=False)) for d in datas]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)


class TestDecodeMany:
    @given(
        k=st.integers(2, 6),
        p=st.integers(1, 3),
        lengths=st.lists(st.integers(0, 6000), min_size=1, max_size=6),
        seed=st.integers(0, 2**31),
    )
    @settings(max_examples=20, deadline=None)
    def test_property_roundtrip_mixed_erasures(self, k, p, lengths, seed):
        rng = np.random.default_rng(seed)
        payloads = _payloads(lengths, seed)
        codec = ECCodec(k, p)
        parts = []
        for pl, chunks in zip(payloads, codec.encode_many(payloads)):
            keep = np.sort(rng.choice(k + p, size=k, replace=False))
            parts.append((chunks[keep], keep, len(pl)))
        got = codec.decode_many(parts)
        want = [codec.decode(*part) for part in parts]
        assert got == want == payloads

    def test_systematic_fast_path_no_kernel(self):
        """All-systematic items decode with zero launches or matrix work."""
        codec = ECCodec(3, 2)
        payloads = _payloads([4000, 2000], seed=5)
        chunks = codec.encode_many(payloads)
        rows = np.arange(3)
        ops.reset_matrix_caches()
        before = core_shapes.compile_cache_stats()["kernels"].get(
            ops.CENSUS_KERNEL, {"calls": 0}
        )["calls"]
        got = codec.decode_many(
            [(c[:3], rows, len(pl)) for c, pl in zip(chunks, payloads)]
        )
        after = core_shapes.compile_cache_stats()["kernels"].get(
            ops.CENSUS_KERNEL, {"calls": 0}
        )["calls"]
        assert got == payloads
        assert after == before
        assert ops.matrix_cache_stats()["decode_builds"] == 0

    def test_groups_by_erasure_pattern(self):
        """Items sharing a survivor pattern share one decode launch."""
        codec = ECCodec(4, 2)
        payloads = _payloads([3000, 3000, 3000], seed=9)
        chunks = codec.encode_many(payloads)
        rows_a = np.array([1, 2, 4, 5])  # two items on pattern a
        rows_b = np.array([0, 2, 3, 5])
        parts = [
            (chunks[0][rows_a], rows_a, len(payloads[0])),
            (chunks[1][rows_b], rows_b, len(payloads[1])),
            (chunks[2][rows_a], rows_a, len(payloads[2])),
        ]
        ops.reset_matrix_caches()
        assert codec.decode_many(parts) == payloads
        assert ops.matrix_cache_stats()["decode_builds"] == 2  # a and b


class TestMatrixCache:
    def test_repeated_decode_builds_matrix_once(self):
        """The satellite regression: N decodes of one erasure pattern pay
        the Gauss-Jordan inversion exactly once (the counter hook)."""
        codec = ECCodec(4, 2)
        payload = _payloads([5000], seed=1)[0]
        chunks = codec.encode(payload)
        keep = np.array([1, 3, 4, 5])
        ops.reset_matrix_caches()
        for _ in range(5):
            assert codec.decode(chunks[keep], keep, len(payload)) == payload
        stats = ops.matrix_cache_stats()
        assert stats["decode_builds"] == 1
        assert stats["decode_cache"]["hits"] == 4

    def test_repeated_encode_builds_matrix_once(self):
        codec = ECCodec(5, 3)
        payloads = _payloads([100, 200, 300], seed=2)
        ops.reset_matrix_caches()
        for pl in payloads:
            codec.encode(pl)
        codec.encode_many(payloads)
        assert ops.matrix_cache_stats()["encode_builds"] == 1

    def test_decode_cache_is_lru_bounded(self):
        """More erasure patterns than MATRIX_CACHE_SIZE: the cache must
        evict (bounded memory) and rebuild on re-miss, never grow."""
        k, p = 3, 13  # C(16, 3) = 560 patterns > 256
        patterns = list(itertools.combinations(range(k + p), k))
        assert len(patterns) > ops.MATRIX_CACHE_SIZE
        ops.reset_matrix_caches()
        for rows in patterns:
            ops._decode_matrices(k, p, rows)
        stats = ops.matrix_cache_stats()
        assert stats["decode_builds"] == len(patterns)
        assert stats["decode_cache"]["size"] <= ops.MATRIX_CACHE_SIZE
        # the earliest pattern was evicted: touching it again rebuilds
        ops._decode_matrices(k, p, patterns[0])
        assert ops.matrix_cache_stats()["decode_builds"] == len(patterns) + 1

    def test_cached_matrices_are_readonly(self):
        cauchy, _ = ops._encode_matrices(4, 2)
        with pytest.raises(ValueError):
            cauchy[0, 0] = 1


class TestCompileCensus:
    def test_one_compile_per_bucket_rung(self):
        """Steady-state cohorts that land in one (K, P, bucket) rung
        issue exactly one kernel signature; repeats issue none."""
        k, p = 9, 5  # (K, P) unused elsewhere in the suite
        codec = ECCodec(k, p)
        payloads = _payloads([4000, 4100, 3900], seed=4)
        before = core_shapes.issued_shapes(ops.CENSUS_KERNEL)
        codec.encode_many(payloads)  # first launch: one new signature
        issued = core_shapes.issued_shapes(ops.CENSUS_KERNEL)
        assert len(issued - before) == 1
        # same cohort widths -> same bucket -> zero new signatures
        codec.encode_many(payloads)
        codec.encode_many(list(reversed(payloads)))
        assert core_shapes.issued_shapes(ops.CENSUS_KERNEL) == issued


class TestPlanCohorts:
    def test_partitions_in_first_appearance_order(self):
        specs = [(3, 2), (6, 3), (3, 2), (4, 2), (6, 3), (3, 2)]
        got = plan_cohorts(specs)
        assert got == [
            ((3, 2), [0, 2, 5]),
            ((6, 3), [1, 4]),
            ((4, 2), [3]),
        ]

    def test_empty(self):
        assert plan_cohorts([]) == []


class TestEmptyPayload:
    """Satellite regression: zero-length payloads get a well-defined
    empty manifest everywhere instead of a kernel-shape crash."""

    def test_encode_empty_shape(self):
        codec = ECCodec(4, 2)
        chunks = codec.encode(b"")
        assert chunks.shape == (6, 0)
        assert chunks.dtype == np.uint8

    def test_decode_empty_roundtrip(self):
        codec = ECCodec(4, 2)
        chunks = codec.encode(b"")
        keep = np.array([0, 2, 4, 5])
        assert codec.decode(chunks[keep], keep, 0) == b""

    def test_encode_many_mixed_empty(self):
        codec = ECCodec(3, 1)
        got = codec.encode_many([b"", b"abc", b""])
        assert got[0].shape == (4, 0)
        assert got[2].shape == (4, 0)
        np.testing.assert_array_equal(got[1], codec.encode(b"abc"))

    def test_decode_many_mixed_empty(self):
        codec = ECCodec(3, 1)
        payloads = [b"", b"some payload bytes"]
        chunks = codec.encode_many(payloads)
        keep = np.array([0, 1, 3])
        parts = [(c[keep], keep, len(pl)) for c, pl in zip(chunks, payloads)]
        assert codec.decode_many(parts) == payloads


class TestX64ScopeIsThreadLocal:
    """The decision kernels hold ``jax.enable_x64`` on the placing
    thread while the checkpoint pipeline codes on others; Mosaic rejects
    the 64-bit grid indices an x64 scope would give a coding launch, so
    the scope must not leak across threads."""

    def test_encode_on_other_thread_sees_no_x64(self):
        import threading

        import jax

        data = np.random.default_rng(3).integers(0, 256, (4, 3000), np.uint8)
        got: dict = {}

        def worker():
            got["x64"] = jax.config.jax_enable_x64
            got["parity"] = np.asarray(ops.encode_chunks(data, 2, interpret=True))

        with jax.enable_x64(True):
            assert jax.config.jax_enable_x64
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=120)
        assert not t.is_alive()
        assert got["x64"] is False
        want = np.asarray(ops.encode_chunks(data, 2, use_kernel=False))
        np.testing.assert_array_equal(got["parity"], want)
