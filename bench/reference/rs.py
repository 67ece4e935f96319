"""Plain reference of the systematic Cauchy Reed-Solomon code over GF(2^8).

Field polynomial x^8 + x^4 + x^3 + x^2 + 1 (0x11d); the parity matrix is
the P x K Cauchy matrix ``C[i, j] = 1 / (i xor (P + j))``; a group's
K + P chunk rows are its K data rows followed by ``C @ data``.  Numpy
table lookups, independent of the code under test.
"""

from __future__ import annotations

import numpy as np


def _tables():
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= 0x11D
    exp[255:510] = exp[:255]
    return exp, log


EXP, LOG = _tables()


#: ``MUL[a, b] = a * b`` over the field, for every pair of bytes.
MUL = np.zeros((256, 256), dtype=np.uint8)
MUL[1:, 1:] = EXP[LOG[1:, None] + LOG[None, 1:]]


def inv(a: int) -> int:
    return int(EXP[255 - LOG[a]])


def cauchy(p: int, k: int) -> np.ndarray:
    return np.array([[inv(i ^ (p + j)) for j in range(k)] for i in range(p)],
                    dtype=np.int64)


def encode(data: np.ndarray, p: int) -> np.ndarray:
    """(K, B) data bytes -> (P, B) parity bytes."""
    k = data.shape[0]
    c = cauchy(p, k)
    out = np.zeros((p, data.shape[1]), dtype=np.uint8)
    for i in range(p):
        for j in range(k):
            out[i] ^= MUL[c[i, j]][data[j]]
    return out
