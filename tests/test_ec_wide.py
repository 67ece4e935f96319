"""Wide Reed-Solomon stripes, as D-Rex SC picks them on a fleet of
thousands of drives: (K, P) = (18, 3), (39, 4) and (69, 5).  The codec's
cohort path, the kernel entry point's multi-matrix path and the Pallas
kernel (interpret mode) are held bit for bit to a plain GF(2^8) Cauchy
code written here, independent of the program."""

import numpy as np
import pytest

from repro.ec import ECCodec
from repro.kernels import ops
from repro.kernels.rs_bitmatmul import DEFAULT_BLOCK_BYTES

WIDE = [(18, 3), (39, 4), (69, 5)]


def _field_tables():
    """exp/log tables of GF(2^8) over x^8 + x^4 + x^3 + x^2 + 1."""
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i], log[x] = x, i
        x <<= 1
        if x & 0x100:
            x ^= 0x11D
    exp[255:510] = exp[:255]
    return exp, log


EXP, LOG = _field_tables()


def _mul(a: int, col: np.ndarray) -> np.ndarray:
    """``a * col`` over the field, for a byte ``a`` and a byte row."""
    out = np.zeros_like(col)
    nz = col != 0
    if a:
        out[nz] = EXP[LOG[a] + LOG[col[nz]]]
    return out


def plain_parity(data: np.ndarray, p: int) -> np.ndarray:
    """(P, B) parity of (K, B) data rows: ``C @ data`` with the Cauchy
    matrix ``C[i, j] = 1 / (i xor (P + j))``."""
    k = data.shape[0]
    out = np.zeros((p, data.shape[1]), dtype=np.int64)
    for i in range(p):
        for j in range(k):
            inv = int(EXP[255 - LOG[i ^ (p + j)]])
            out[i] ^= _mul(inv, data[j].astype(np.int64))
    return out.astype(np.uint8)


def _payloads(lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=n, dtype=np.uint8) for n in lengths]


def _rows(payload: np.ndarray, k: int) -> np.ndarray:
    clen = -(-payload.size // k)
    out = np.zeros(k * clen, dtype=np.uint8)
    out[: payload.size] = payload
    return out.reshape(k, clen)


@pytest.mark.parametrize("k,p", WIDE)
def test_codec_cohort_matches_plain_code(k, p):
    """One cohort of mixed lengths (a bucket, a tail shorter than K rows of
    a block, an empty payload) in one launch; chunks are K data rows then
    the plain code's P parity rows."""
    payloads = _payloads([1 << 16, 3 * k + 1, 0, 40_000], seed=k)
    got = ECCodec(k, p).encode_many(payloads)
    for pl, chunks in zip(payloads, got):
        data = _rows(pl, k)
        assert chunks.shape == (k + p, data.shape[1])
        np.testing.assert_array_equal(chunks[:k], data)
        np.testing.assert_array_equal(chunks[k:], plain_parity(data, p))


def test_mixed_cohorts_back_to_back():
    """Cohorts of every wide (K, P) interleaved with the benchmark's (4, 2),
    as a save's waves run, each through the multi-matrix entry point."""
    for round_ in range(2):
        for k, p in WIDE + [(4, 2)]:
            mats = [_rows(pl, k) for pl in _payloads([9000, 2048 * k, 77], seed=round_ * 100 + k)]
            got = ops.encode_chunks_many(mats, p)
            assert len(got) == len(mats)
            for m, par in zip(mats, got):
                np.testing.assert_array_equal(np.asarray(par), plain_parity(m, p))


@pytest.mark.parametrize("k,p", WIDE)
def test_pallas_kernel_interpreted_matches_plain_code(k, p):
    """The Pallas bit-matmul itself, at the default byte tile, over two
    tiles of a cohort of two matrices."""
    mats = [_rows(pl, k) for pl in _payloads([DEFAULT_BLOCK_BYTES * k, 500], seed=p)]
    got = ops.encode_chunks_many(mats, p, interpret=True)
    for m, par in zip(mats, got):
        np.testing.assert_array_equal(np.asarray(par), plain_parity(m, p))
