"""Pallas TPU kernel: Cauchy-RS coding as a mod-2 bit-matrix MXU matmul.

TPU adaptation of the paper's GF(2^8) hot-spot: instead of
the CPU's 256-entry lookup-table gathers (hostile to the MXU), the code's
GF(2)-linearity turns encode/decode into one dense {0,1} matmul

    out_bits (8R, B) = bit_matrix (8R, 8K) @ data_bits (8K, B)   (mod 2)

evaluated in f32 on the systolic array (sums <= 8K <= 2048 are exact in
f32). HBM traffic stays at byte granularity: the 8x bit inflation happens
in VMEM after the tile load, and parity bits are re-packed to bytes
before the store.

Grid: 1-D over byte columns. Per-program VMEM working set for block size
``bb`` and K data chunks: K*bb (input bytes) + 8K*bb*4 (bits f32) +
8R*bb*4 (acc) + R*bb (output) bytes — for K=16, R=16, bb=2048 that is
~2.3 MB, comfortably inside a v5e's ~16 MB VMEM with double-buffering.

The byte dimension block (lane dimension) is kept a multiple of 128; the
bit dimensions (8K, 8R) are multiples of 8 and are padded by Mosaic to
the MXU's 128 where needed — for the small K of storage codes the MXU is
underutilized in one dimension, which is intrinsic to the problem shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_BYTES = 2048


def _coding_kernel(bitm_ref, data_ref, out_ref, *, r: int, k: int):
    """One byte-tile: unpack -> f32 MXU matmul -> mod 2 -> pack."""
    d = data_ref[...].astype(jnp.int32)                       # (K, bb)
    shifts = jnp.arange(8, dtype=jnp.int32)
    bits = (d[:, None, :] >> shifts[None, :, None]) & 1       # (K, 8, bb)
    bits = bits.reshape(8 * k, d.shape[-1]).astype(jnp.float32)
    bm = bitm_ref[...]                                        # (8R, 8K) f32
    acc = jnp.dot(bm, bits, preferred_element_type=jnp.float32)
    par_bits = acc.astype(jnp.int32) & 1                      # exact mod-2
    par_bits = par_bits.reshape(r, 8, d.shape[-1])
    weights = (jnp.int32(1) << shifts).astype(jnp.int32)
    packed = (par_bits * weights[None, :, None]).sum(axis=1)
    out_ref[...] = packed.astype(jnp.uint8)


@functools.partial(
    jax.jit, static_argnames=("block_bytes", "interpret")
)
def gf_bitmatmul(
    bit_matrix: jax.Array,
    data_chunks: jax.Array,
    *,
    block_bytes: int = DEFAULT_BLOCK_BYTES,
    interpret: bool = False,
) -> jax.Array:
    """out (R, B) u8 = GF(2^8) matrix-product via bit-matmul.

    ``bit_matrix``: (8R, 8K) f32 in {0,1} (from gf_to_bitmatrix).
    ``data_chunks``: (K, B) uint8, B a multiple of ``block_bytes``
    (ops.py pads).  ``interpret=True`` runs the Pallas interpreter (the
    CPU correctness tests ask for it); it is never chosen automatically.
    Call outside any ``jax.enable_x64`` scope: Mosaic rejects the 64-bit
    grid indices such a scope produces.
    """
    r8, k8 = bit_matrix.shape
    assert r8 % 8 == 0 and k8 % 8 == 0, bit_matrix.shape
    r, k = r8 // 8, k8 // 8
    kk, b = data_chunks.shape
    assert kk == k, (data_chunks.shape, bit_matrix.shape)
    assert b % block_bytes == 0, (b, block_bytes)
    grid = (b // block_bytes,)

    return pl.pallas_call(
        functools.partial(_coding_kernel, r=r, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((r8, k8), lambda i: (0, 0)),          # whole matrix
            pl.BlockSpec((k, block_bytes), lambda i: (0, i)),  # byte tile
        ],
        out_specs=pl.BlockSpec((r, block_bytes), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((r, b), jnp.uint8),
        interpret=interpret,
    )(bit_matrix.astype(jnp.float32), data_chunks)
