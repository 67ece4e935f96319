"""Item-level erasure codec: bytes -> N chunks -> bytes (with erasures).

Wraps the chunk-matrix kernels with the split/pad/join bookkeeping the
checkpoint manager and benchmarks need.  A ``ECCodec(k, p)`` is the data
plane counterpart of a :class:`repro.core.types.Placement`.

Batch API: :meth:`ECCodec.encode_many` / :meth:`ECCodec.decode_many`
drive whole cohorts of payloads through one kernel launch per coding
matrix (see ``repro.kernels.ops``), and the module-level planner
(:func:`plan_cohorts` / :func:`encode_batch`) partitions a mixed list of
``(k, p)`` codings into those cohorts.  The per-item :meth:`ECCodec.
encode` / :meth:`ECCodec.decode` path is the bit-for-bit oracle the
batched paths are pinned against (tests/test_ec_batched.py).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from repro import telemetry
from repro.kernels import ops as kops

__all__ = [
    "ECCodec",
    "encode_item",
    "decode_item",
    "plan_cohorts",
    "encode_batch",
]


def _as_bytes_array(payload) -> np.ndarray:
    if isinstance(payload, (bytes, bytearray, memoryview)):
        return np.frombuffer(bytes(payload), dtype=np.uint8)
    return np.asarray(payload, dtype=np.uint8).ravel()


def _stage_rows(rows: np.ndarray, buf: np.ndarray) -> None:
    """Copy one payload into its (K, chunk_len) block of a wave buffer's
    data rows, row by row; only the short tail is zeroed."""
    k, clen = rows.shape
    full, rem = divmod(buf.size, clen)
    rows[:full] = buf[: full * clen].reshape(full, clen)
    if full < k:
        rows[full, :rem] = buf[full * clen :]
        rows[full, rem:] = 0
        rows[full + 1 :] = 0


@dataclasses.dataclass(frozen=True)
class ECCodec:
    k: int
    p: int
    use_kernel: bool = True

    @property
    def n(self) -> int:
        return self.k + self.p

    def chunk_len(self, nbytes: int) -> int:
        return -(-nbytes // self.k)  # ceil(size / K), paper Table 1

    def _data_matrix(self, payload) -> np.ndarray:
        """(K, chunk_len) zero-padded data rows for one payload."""
        buf = _as_bytes_array(payload)
        clen = self.chunk_len(buf.size)
        padded = np.zeros(self.k * clen, dtype=np.uint8)
        padded[: buf.size] = buf
        return padded.reshape(self.k, clen)

    def encode(self, payload: bytes | np.ndarray) -> np.ndarray:
        """bytes -> (N, chunk_len) uint8: K data rows then P parity rows.

        An empty payload yields a well-defined empty manifest — shape
        (N, 0), no kernel call (the kernels require block-aligned widths
        and an empty matrix has none)."""
        data = self._data_matrix(payload)
        if data.shape[1] == 0:
            return np.zeros((self.n, 0), dtype=np.uint8)
        parity = np.asarray(
            kops.encode_chunks(data, self.p, use_kernel=self.use_kernel)
        )
        return np.concatenate([data, parity], axis=0)

    def wave_nbytes(self, sizes: Sequence[int]) -> int:
        """Bytes of the wave buffer :meth:`encode_many` needs for payloads
        of these sizes: N rows of the summed chunk lengths."""
        return self.n * sum(self.chunk_len(s) for s in sizes)

    def encode_many(self, payloads: Sequence, out: np.ndarray | None = None) -> list[np.ndarray]:
        """Encode a cohort of payloads in ONE kernel launch.

        Payload lengths may differ (the code is columnwise; the kernel
        sees the cohort side by side along the byte axis).  The cohort
        lives in one (N, W) wave buffer, W the summed chunk lengths: the
        payloads are staged into its K data rows, which go to the device
        as they are, and the parity comes back into its P parity rows.
        ``out`` is that buffer, a flat uint8 array of at least
        :meth:`wave_nbytes` bytes that the caller owns and may reuse once
        it is done with the chunks (a fresh one when None).

        Returns the (N, chunk_len_i) chunk matrices in input order, each a
        column view of the buffer (every row contiguous), bit-identical to
        per-item :meth:`encode`."""
        bufs = [_as_bytes_array(p) for p in payloads]
        clens = [self.chunk_len(b.size) for b in bufs]
        width = sum(clens)
        if out is None:
            out = np.empty(self.n * width, dtype=np.uint8)
        if (out.dtype != np.uint8 or out.ndim != 1 or not out.flags.c_contiguous
                or out.size < self.n * width):
            raise ValueError(
                f"wave buffer must be flat contiguous uint8 of at least "
                f"{self.n * width} bytes, got {out.dtype} {out.shape}"
            )
        wave = out[: self.n * width].reshape(self.n, width)
        offs = np.cumsum([0] + clens).tolist()
        with telemetry.span("codec.stage", self.k * width):
            for buf, a, b in zip(bufs, offs, offs[1:]):
                if b > a:
                    _stage_rows(wave[: self.k, a:b], buf)
        if width:
            (parity,) = kops.encode_chunks_many(
                [wave[: self.k]], self.p, use_kernel=self.use_kernel
            )
            with telemetry.span("codec.assemble", self.p * width):
                wave[self.k :] = parity
        return [wave[:, a:b] for a, b in zip(offs, offs[1:])]

    def _select_rows(
        self, chunks: np.ndarray, rows: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Deterministic choice of K rows (sorted; systematic first)."""
        chunks = np.asarray(chunks, dtype=np.uint8)
        rows = np.asarray(rows)
        if chunks.shape[0] < self.k:
            raise ValueError(
                f"need at least K={self.k} chunks, got {chunks.shape[0]}"
            )
        sel = np.argsort(rows)[: self.k]
        return chunks[sel], rows[sel]

    def decode(
        self,
        chunks: np.ndarray,
        rows: np.ndarray,
        orig_nbytes: int,
    ) -> bytes:
        """Any K chunk rows (+ their row indices) -> original payload."""
        use_chunks, use_rows = self._select_rows(chunks, rows)
        if orig_nbytes == 0 or use_chunks.shape[1] == 0:
            return b""
        if np.array_equal(use_rows, np.arange(self.k)):
            data = use_chunks  # all-systematic fast path: no math
        else:
            data = np.asarray(
                kops.decode_chunks(
                    use_chunks, use_rows, self.k, self.p,
                    use_kernel=self.use_kernel,
                )
            )
        return data.reshape(-1)[:orig_nbytes].tobytes()

    def decode_many(
        self, parts: Sequence[tuple[np.ndarray, np.ndarray, int]]
    ) -> list[bytes]:
        """Decode a cohort of ``(chunks, rows, orig_nbytes)`` triples.

        All-systematic items take the no-math fast path; the rest run
        one kernel launch per distinct erasure pattern.  Bit-identical
        to per-item :meth:`decode`."""
        outs: list = [None] * len(parts)
        pend_idx: list[int] = []
        pend_chunks: list[np.ndarray] = []
        pend_rows: list[np.ndarray] = []
        systematic = np.arange(self.k)
        for i, (chunks, rows, orig_nbytes) in enumerate(parts):
            use_chunks, use_rows = self._select_rows(chunks, rows)
            if orig_nbytes == 0 or use_chunks.shape[1] == 0:
                outs[i] = b""
            elif np.array_equal(use_rows, systematic):
                outs[i] = use_chunks.reshape(-1)[:orig_nbytes].tobytes()
            else:
                pend_idx.append(i)
                pend_chunks.append(use_chunks)
                pend_rows.append(use_rows)
        if pend_idx:
            datas = kops.decode_chunks_many(
                pend_chunks, pend_rows, self.k, self.p,
                use_kernel=self.use_kernel,
            )
            for i, data in zip(pend_idx, datas):
                nbytes = parts[i][2]
                outs[i] = np.asarray(data).reshape(-1)[:nbytes].tobytes()
        return outs


def plan_cohorts(specs: Sequence[tuple[int, int]]) -> list[tuple[tuple[int, int], list[int]]]:
    """Partition payload indices by codec shape.

    ``specs[i] = (k, p)`` for payload i; returns ``[((k, p), indices),
    ...]`` in first-appearance order — each cohort shares one coding
    matrix and therefore one kernel launch."""
    order: dict[tuple[int, int], list[int]] = {}
    for i, (k, p) in enumerate(specs):
        order.setdefault((int(k), int(p)), []).append(i)
    return list(order.items())


def encode_batch(
    specs: Sequence[tuple[int, int]],
    payloads: Sequence,
    *,
    use_kernel: bool = True,
) -> list[np.ndarray]:
    """Encode a mixed-(K, P) batch: one launch per (K, P) cohort.

    Returns the (N_i, chunk_len_i) chunk matrices in input order."""
    if len(specs) != len(payloads):
        raise ValueError("specs/payloads length mismatch")
    outs: list = [None] * len(payloads)
    for (k, p), idxs in plan_cohorts(specs):
        codec = ECCodec(k, p, use_kernel=use_kernel)
        for i, chunks in zip(idxs, codec.encode_many([payloads[i] for i in idxs])):
            outs[i] = chunks
    return outs


def encode_item(payload: bytes, k: int, p: int, use_kernel: bool = True) -> np.ndarray:
    return ECCodec(k, p, use_kernel).encode(payload)


def decode_item(
    chunks: np.ndarray,
    rows: np.ndarray,
    k: int,
    p: int,
    orig_nbytes: int,
    use_kernel: bool = True,
) -> bytes:
    return ECCodec(k, p, use_kernel).decode(chunks, rows, orig_nbytes)
