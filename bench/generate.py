"""Inputs of every cell: the cluster and the checkpointed state.

Everything comes from ``--seed`` and the data files alone; the program
only receives what is made here.
"""

from __future__ import annotations

import numpy as np

TB_MB = 1_000_000.0


def rng_for(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), salt])


# -- clusters -------------------------------------------------------------------

def cluster_arrays(cluster: dict) -> dict[str, np.ndarray]:
    """Node arrays of a configuration's cluster (MB, MB/s, AFR): one node
    per row, empty at the start."""
    if cluster["kind"] != "nodes":
        raise ValueError(f"unknown cluster kind {cluster['kind']!r}")
    rows = cluster["rows"]  # [name, capacity TB, write MB/s, read MB/s, AFR]
    col = lambda j: np.array([float(row[j]) for row in rows])  # noqa: E731
    cap = col(1) * TB_MB
    n = len(rows)
    return {
        "names": [row[0] for row in rows],
        "capacity_mb": cap,
        "used_mb": np.zeros(n),
        "write_bw": col(2),
        "read_bw": col(3),
        "afr": col(4),
        "rack": np.zeros(n, dtype=np.int64),
        "zone": np.zeros(n, dtype=np.int64),
    }


# -- checkpoint state ---------------------------------------------------------------

def shard_leaves(state: dict) -> list[tuple[str, int]]:
    """(name, parameters) of every leaf of one chip's FSDP shard of a
    decoder-only model's training state, in save order."""
    h, f = state["hidden_size"], state["intermediate_size"]
    q = state["num_attention_heads"] * state["head_dim"]
    kv = state["num_key_value_heads"] * state["head_dim"]
    per_layer = [
        ("q_proj", h * q), ("k_proj", h * kv), ("v_proj", h * kv), ("o_proj", q * h),
        ("gate_proj", h * f), ("up_proj", h * f), ("down_proj", f * h),
        ("input_layernorm", h), ("post_attention_layernorm", h),
    ]
    ways = state["fsdp_ways"]
    out = []
    for layer in range(state["num_hidden_layers"]):
        for copy in state["copies"]:
            for name, n in per_layer:
                if n % ways:
                    raise ValueError(f"{name}: {n} parameters do not split {ways} ways")
                out.append((f"layers.{layer}.{name}.{copy}", n // ways))
    return out


class StateMaker:
    """One jitted program that makes a whole shard on the device from
    ``(seed, step)``: float32 normals, one scale per optimizer copy."""

    def __init__(self, state: dict, scales: dict, seed: int):
        import jax
        import jax.numpy as jnp

        self.leaves = shard_leaves(state)
        scales = [float(scales[name.rsplit(".", 1)[1]]) for name, _ in self.leaves]
        sizes = [n for _, n in self.leaves]
        base = jax.random.key(int(seed) & 0xFFFFFFFF)
        self._base = jax.random.fold_in(base, (int(seed) >> 32) & 0xFFFFFFFF)

        def make(key):
            return [
                jax.random.normal(jax.random.fold_in(key, i), (n,), jnp.float32) * s
                for i, (n, s) in enumerate(zip(sizes, scales))
            ]

        self._make = jax.jit(make)
        self._fold = jax.jit(jax.random.fold_in)
        self.nbytes = 4 * sum(sizes)
        self.like = [jax.ShapeDtypeStruct((n,), jnp.float32) for n in sizes]

    def __call__(self, step: int):
        import jax

        out = self._make(self._fold(self._base, step))
        jax.block_until_ready(out)
        return out
