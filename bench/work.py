"""Useful HBM bytes of the Reed-Solomon coding work, from a manifest.

A group of ``orig_nbytes`` user bytes coded as K data and P parity rows
has the unpadded chunk length ``B = ceil(orig_nbytes / K)``.  Encoding
reads its K data rows and writes P parity rows: ``(K + P) * B`` bytes.
The count depends only on (K, P, B), never on how a kernel is written;
padding the kernel adds to its time and not to these bytes.
"""

from __future__ import annotations


def chunk_len(orig_nbytes: int, k: int) -> int:
    return -(-int(orig_nbytes) // int(k))


def encode_bytes(k: int, p: int, orig_nbytes: int) -> int:
    return (k + p) * chunk_len(orig_nbytes, k)


def groups(manifest: dict) -> list[dict]:
    return [g for leaf in manifest["leaves"] if leaf for g in leaf["groups"]]


def save_encode_bytes(manifest: dict) -> int:
    """Useful bytes of every encode of one save."""
    return sum(encode_bytes(g["k"], g["p"], g["orig_nbytes"]) for g in groups(manifest))
