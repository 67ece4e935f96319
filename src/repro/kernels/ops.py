"""Jit'd public wrappers over the coding kernels.

``encode_chunks`` / ``decode_chunks`` operate on (K, B) byte matrices;
``encode_chunks_many`` / ``decode_chunks_many`` batch whole cohorts of
same-shape codings into ONE kernel launch; ``repro.ec.codec`` builds the
item-level API (split/pad/join, chunk manifests) on top of these.

Three data-plane optimizations live here (everything above sees only
bytes in, bytes out, bit-identical to the per-item oracle):

* **Cached coding matrices.**  The host-side Cauchy / decode matrices
  and their GF(2) bit-matrix expansions are pure functions of ``(k, p)``
  (encode) and ``(k, p, surviving_rows)`` (decode) — memoized in
  process-wide LRU caches so steady-state encode/repair stops rebuilding
  the same tiny matrices (``gf_mat_inv`` is Python-loop pivoting) on
  every call.  ``matrix_cache_stats`` exposes build/hit counters.

* **Multi-item launches.**  The coding kernels are linear per byte
  column: ``M @ [D1 | D2 | ...] == [M@D1 | M@D2 | ...]``, so a cohort of
  groups sharing a bit matrix concatenates along the byte axis into one
  launch — one dispatch instead of one per group, and the f32
  bit-accumulation stays exact (sums <= 8K <= 2048), so batched output
  is *bit-identical* to the per-item path by construction.

* **Shape buckets.**  The byte axis is padded to a bucketed block count
  (:func:`repro.core.shapes.ec_block_pad` — the same rung/hysteresis
  planner the placement kernels share) so churn in cohort sizes does not
  churn XLA compiles; every launch records its static signature through
  the shared compile census (``compile_cache_stats``).

Backend dispatch: on the TPU the kernel path always runs the Pallas
bit-matmul on the MXU.  On the CPU backend it runs the jitted, tiled XLA
twin (``ref.bitmatmul_ref`` under ``jax.jit``) — the same
unpack/matmul/pack algorithm.  ``interpret=True`` runs the Pallas kernel
in the Pallas interpreter instead (the CPU correctness harness in
tests/test_kernels.py asks for it); interpret mode is never chosen
automatically.
"""

from __future__ import annotations

import functools
import threading as _threading

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import shapes as _shapes
from repro.ec import gf256
from .rs_bitmatmul import DEFAULT_BLOCK_BYTES, gf_bitmatmul
from . import ref as _ref

__all__ = [
    "encode_chunks",
    "decode_chunks",
    "encode_chunks_many",
    "decode_chunks_many",
    "encode_chunks_ref",
    "decode_chunks_ref",
    "matrix_cache_stats",
    "reset_matrix_caches",
    "MATRIX_CACHE_SIZE",
]

#: LRU bound on the decode-matrix cache: (k, p, surviving_rows) patterns
#: are combinatorial, so unlike the (k, p) encode cache the decode cache
#: must evict.  256 distinct erasure patterns covers steady-state repair
#: of any realistic failure mix; eviction just means a rebuild.
MATRIX_CACHE_SIZE = 256

#: kernel name under which every coding launch records its static
#: signature in the shared compile census (repro.core.shapes).
CENSUS_KERNEL = "rs_bitmatmul"

#: build counters behind the LRU caches (the counter hook the cache
#: tests pin "built exactly once" against).  ``lru_cache`` does NOT hold
#: its lock while the wrapped builder runs, so two threads missing the
#: same key concurrently (the serve frontier's worker threads do) both
#: execute the builder — a bare ``+= 1`` here is a read-modify-write
#: race that loses increments.  All counter updates go through
#: :func:`_note_build` under ``_builds_lock``; regression:
#: tests/test_threaded_counters.py.
_MATRIX_BUILDS = {"encode": 0, "decode": 0}
_builds_lock = _threading.Lock()


def _note_build(kind: str) -> None:
    with _builds_lock:
        _MATRIX_BUILDS[kind] += 1


@functools.lru_cache(maxsize=MATRIX_CACHE_SIZE)
def _encode_matrices(k: int, p: int):
    """(Cauchy GF matrix, (8P, 8K) f32 bit matrix) for encode — cached.

    The numpy matrix is returned read-only: cached arrays are shared."""
    _note_build("encode")
    cauchy = gf256.cauchy_matrix(p, k)
    cauchy.setflags(write=False)
    bitm = jnp.asarray(gf256.gf_to_bitmatrix(cauchy), dtype=jnp.float32)
    return cauchy, bitm


@functools.lru_cache(maxsize=MATRIX_CACHE_SIZE)
def _decode_matrices(k: int, p: int, rows: tuple):
    """(decode GF matrix, (8K, 8K) f32 bit matrix) for one erasure
    pattern — cached so repeated decodes of the same pattern pay the
    Gauss-Jordan inversion exactly once."""
    _note_build("decode")
    dec = gf256.decode_matrix(k, p, np.asarray(rows, dtype=np.int64))
    dec.setflags(write=False)
    bitm = jnp.asarray(gf256.gf_to_bitmatrix(dec), dtype=jnp.float32)
    return dec, bitm


def matrix_cache_stats() -> dict:
    """Telemetry: matrix builds vs cache hits (see MATRIX_CACHE_SIZE)."""
    enc, dec = _encode_matrices.cache_info(), _decode_matrices.cache_info()
    with _builds_lock:
        encode_builds = _MATRIX_BUILDS["encode"]
        decode_builds = _MATRIX_BUILDS["decode"]
    return {
        "encode_builds": encode_builds,
        "decode_builds": decode_builds,
        "encode_cache": {"hits": enc.hits, "misses": enc.misses,
                         "size": enc.currsize, "maxsize": enc.maxsize},
        "decode_cache": {"hits": dec.hits, "misses": dec.misses,
                         "size": dec.currsize, "maxsize": dec.maxsize},
    }


def reset_matrix_caches() -> None:
    """Clear the matrix caches and build counters (tests)."""
    _encode_matrices.cache_clear()
    _decode_matrices.cache_clear()
    with _builds_lock:
        _MATRIX_BUILDS["encode"] = 0
        _MATRIX_BUILDS["decode"] = 0


def _rows_key(surviving_rows) -> tuple:
    return tuple(int(r) for r in np.asarray(surviving_rows).reshape(-1))


# -- one launch: pad -> census -> matmul -------------------------------------

#: column tile (in byte blocks) for the XLA twin of the Pallas kernel.
#: ``lax.map`` over cache-sized tiles keeps each tile's unpacked f32 bit
#: planes resident while it is consumed; a monolithic launch at
#: checkpoint-cohort widths materializes tens of MB of intermediates and
#: runs ~4x slower (measured in benchmarks/fig1's batched lane).  The
#: Pallas kernel needs no analogue — its grid over ``block_bytes``
#: blocks IS the tiling.
EC_TILE_BLOCKS = 2


@functools.partial(jax.jit, static_argnames=("block_bytes",))
def _bitmatmul_xla(bitm, data, *, block_bytes: int = DEFAULT_BLOCK_BYTES):
    k, b = data.shape
    tile = EC_TILE_BLOCKS * block_bytes
    # Bucketed widths are powers of two below 8 blocks and multiples of
    # 8 blocks above (shapes.ec_block_pad), so any width > tile divides
    # evenly; the guard keeps the function total for direct callers.
    if b <= tile or b % tile:
        return _ref.bitmatmul_ref(bitm, data)
    n_tiles = b // tile
    tiles = data.reshape(k, n_tiles, tile).transpose(1, 0, 2)
    out = jax.lax.map(lambda t: _ref.bitmatmul_ref(bitm, t), tiles)
    return out.transpose(1, 0, 2).reshape(out.shape[1], b)


def _pad_to_bucket(data: jax.Array, block: int) -> tuple[jax.Array, int]:
    """Pad the byte axis to a *bucketed* multiple of ``block`` (zeros)."""
    k, b = data.shape
    blocks = -(-b // block)  # ceil; at least 1 block so grids are nonempty
    target = _shapes.ec_block_pad(max(1, blocks)) * block
    if target != b:
        data = jnp.pad(data, ((0, 0), (0, target - b)))
    return data, b


def _bitmatmul(
    bitm: jax.Array,
    data: jax.Array,
    *,
    block_bytes: int,
    interpret: bool,
) -> jax.Array:
    """One coding launch on a block-aligned (K, B) byte matrix: Pallas on
    the TPU (or interpreted, when asked), the XLA twin on the CPU."""
    if interpret:
        path = "pallas-interpret"
    elif jax.default_backend() == "tpu":
        path = "pallas"
    else:
        path = "xla"
    r8, k8 = bitm.shape
    _shapes.record_compile(
        CENSUS_KERNEL,
        (r8, k8, data.shape[1] // block_bytes, block_bytes, path),
    )
    if path == "xla":
        out = _bitmatmul_xla(bitm, data, block_bytes=block_bytes)
    else:
        out = gf_bitmatmul(bitm, data, block_bytes=block_bytes, interpret=interpret)
    _shapes.record_device(CENSUS_KERNEL, out)
    return out


# -- per-item API (the bit-for-bit oracle for the _many paths) ---------------

def encode_chunks(
    data_chunks,
    p: int,
    *,
    block_bytes: int = DEFAULT_BLOCK_BYTES,
    use_kernel: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Parity chunks (P, B) for systematic Cauchy-RS over (K, B) data."""
    data = jnp.asarray(data_chunks, dtype=jnp.uint8)
    k, b = data.shape
    if b == 0:  # empty item: a well-defined empty parity, no kernel call
        return jnp.zeros((p, 0), dtype=jnp.uint8)
    cauchy, bitm = _encode_matrices(k, p)
    if not use_kernel:
        return _ref.encode_ref(data, jnp.asarray(cauchy))
    padded, b = _pad_to_bucket(data, block_bytes)
    out = _bitmatmul(bitm, padded, block_bytes=block_bytes, interpret=interpret)
    return out[:, :b]


def decode_chunks(
    surviving_chunks,
    surviving_rows,
    k: int,
    p: int,
    *,
    block_bytes: int = DEFAULT_BLOCK_BYTES,
    use_kernel: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Reconstruct the K data chunks from any K surviving chunk rows.

    ``surviving_rows``: indices into the N=K+P rows matching the order of
    ``surviving_chunks`` (K, B)."""
    surv = jnp.asarray(surviving_chunks, dtype=jnp.uint8)
    dec, bitm = _decode_matrices(k, p, _rows_key(surviving_rows))
    if surv.shape[1] == 0:
        return jnp.zeros((k, 0), dtype=jnp.uint8)
    if not use_kernel:
        return _ref.decode_ref(surv, jnp.asarray(dec))
    padded, b = _pad_to_bucket(surv, block_bytes)
    out = _bitmatmul(bitm, padded, block_bytes=block_bytes, interpret=interpret)
    return out[:, :b]


# -- multi-item API: one launch per cohort -----------------------------------

def _matmul_concat(
    mats: list[np.ndarray],
    gf_matrix: np.ndarray,
    bitm,
    out_rows: int,
    *,
    block_bytes: int,
    use_kernel: bool,
    interpret: bool,
) -> list[np.ndarray]:
    """Apply one coding matrix to many (K, B_i) matrices in one launch."""
    widths = [m.shape[1] for m in mats]
    outs: list = [None] * len(mats)
    live = [i for i, w in enumerate(widths) if w > 0]
    for i, w in enumerate(widths):
        if w == 0:
            outs[i] = np.zeros((out_rows, 0), dtype=np.uint8)
    if live:
        cat = jnp.asarray(
            np.concatenate([mats[i] for i in live], axis=1), dtype=jnp.uint8
        )
        total = cat.shape[1]
        if use_kernel:
            padded, _ = _pad_to_bucket(cat, block_bytes)
            out = _bitmatmul(
                bitm, padded, block_bytes=block_bytes, interpret=interpret
            )[:, :total]
        else:
            out = _ref.gf_matmul_ref(jnp.asarray(gf_matrix), cat)
        out = np.asarray(out)
        off = 0
        for i in live:
            outs[i] = out[:, off : off + widths[i]]
            off += widths[i]
    return outs


def encode_chunks_many(
    data_chunks_list,
    p: int,
    *,
    block_bytes: int = DEFAULT_BLOCK_BYTES,
    use_kernel: bool = True,
    interpret: bool = False,
) -> list[np.ndarray]:
    """Parity for a cohort of (K, B_i) data matrices sharing K and P.

    The cohort is stacked along the byte axis into ONE kernel launch
    (byte lengths may differ — the code is columnwise); results are
    bit-identical to per-item :func:`encode_chunks`.  Returns a list of
    (P, B_i) numpy arrays in input order."""
    mats = [np.asarray(d, dtype=np.uint8) for d in data_chunks_list]
    if not mats:
        return []
    k = mats[0].shape[0]
    for m in mats:
        if m.shape[0] != k:
            raise ValueError(
                f"cohort mixes K: {m.shape[0]} vs {k} (partition by (K, P) "
                "first — see repro.ec.codec.plan_cohorts)"
            )
    cauchy, bitm = _encode_matrices(k, p)
    return _matmul_concat(
        mats, cauchy, bitm, p,
        block_bytes=block_bytes, use_kernel=use_kernel, interpret=interpret,
    )


def decode_chunks_many(
    surviving_chunks_list,
    surviving_rows_list,
    k: int,
    p: int,
    *,
    block_bytes: int = DEFAULT_BLOCK_BYTES,
    use_kernel: bool = True,
    interpret: bool = False,
) -> list[np.ndarray]:
    """Reconstruct many items sharing (K, P): one launch per distinct
    erasure pattern (the decode matrix depends on the surviving rows).

    Returns a list of (K, B_i) numpy arrays in input order."""
    mats = [np.asarray(c, dtype=np.uint8) for c in surviving_chunks_list]
    if len(mats) != len(surviving_rows_list):
        raise ValueError("chunks/rows length mismatch")
    by_pattern: dict[tuple, list[int]] = {}
    for i, rows in enumerate(surviving_rows_list):
        by_pattern.setdefault(_rows_key(rows), []).append(i)
    outs: list = [None] * len(mats)
    for rows_key, idxs in by_pattern.items():
        dec, bitm = _decode_matrices(k, p, rows_key)
        got = _matmul_concat(
            [mats[i] for i in idxs], dec, bitm, k,
            block_bytes=block_bytes, use_kernel=use_kernel, interpret=interpret,
        )
        for i, out in zip(idxs, got):
            outs[i] = out
    return outs


def encode_chunks_ref(data_chunks, p: int) -> jax.Array:
    """Oracle path (pure jnp log/exp tables)."""
    return encode_chunks(data_chunks, p, use_kernel=False)


def decode_chunks_ref(surviving_chunks, surviving_rows, k: int, p: int) -> jax.Array:
    return decode_chunks(surviving_chunks, surviving_rows, k, p, use_kernel=False)
