"""shard_map MoE dispatch (beyond-paper §Perf optimization, opt-in via
``moe_dispatch="shard_map"``).

Why: under the global-view scatter formulation, GSPMD reduces the FULL
(E, C, D) dispatch buffers across the mesh (the qwen-MoE cells' dominant
collective). With explicit per-shard control the data plane becomes:

  * x is replicated across the model axis within each data shard, so
    "dispatch to the model shard owning expert e" is a local slice — no
    cross-device dispatch traffic at all;
  * each model shard runs its E/n_model experts over the local tokens;
  * the only collective is one psum of the combined token outputs
    (B_loc, T, D) over the model axis per layer — the same volume as a
    single TP all-reduce, orders of magnitude below the buffer reduce.

Capacity semantics: per-(data-shard, expert) queues (local capacity),
the standard large-scale variant of GShard capacity. FSDP'd expert
weights are all-gathered over the data axis explicitly inside the shard
(the gather GSPMD previously inserted implicitly).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig


def _local_moe(xt, router, wg, wi, wo, *, cfg: ModelConfig, n_model: int,
               fsdp_axes):
    """Per-shard body. xt: (S_loc, D); router: (D, Ep) replicated;
    wg/wi/wo: (Ep/n_model, D[/fsdp], F) local expert slices."""
    m = cfg.moe
    ep = m.n_experts_padded
    s_loc, d = xt.shape

    # FSDP: expert weights arrive sharded over the data axes on the embed
    # dim; gather them for local compute (explicitly, once per layer).
    for ax in fsdp_axes:
        wg = jax.lax.all_gather(wg, ax, axis=1, tiled=True)
        wi = jax.lax.all_gather(wi, ax, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, ax, axis=2, tiled=True)

    logits = (xt.astype(jnp.float32) @ router).astype(jnp.float32)
    if ep != m.n_experts:
        logits = jnp.where(jnp.arange(ep)[None, :] >= m.n_experts, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, m.experts_per_token)
    if m.norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    cap = int(math.ceil(s_loc * m.experts_per_token / ep * m.capacity_factor))
    flat_ids = top_ids.reshape(-1)
    flat_w = top_w.reshape(-1)
    one_hot = jax.nn.one_hot(flat_ids, ep, dtype=jnp.int32)
    pos = jnp.cumsum(one_hot, axis=0) * one_hot - one_hot
    slot = jnp.sum(pos, axis=1)
    keep = slot < cap
    slot_c = jnp.where(keep, slot, 0)

    xe = jnp.repeat(xt, m.experts_per_token, axis=0)
    dispatched = jnp.zeros((ep, cap, d), xt.dtype)
    dispatched = dispatched.at[flat_ids, slot_c].add(
        jnp.where(keep[:, None], xe, 0).astype(xt.dtype)
    )

    # keep only this model shard's experts (x is replicated over 'model',
    # so this is a free slice, not a communication)
    e_loc = ep // n_model
    shard = jax.lax.axis_index("model")
    local = jax.lax.dynamic_slice_in_dim(dispatched, shard * e_loc, e_loc, axis=0)

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", local, wg))
    h = g * jnp.einsum("ecd,edf->ecf", local, wi)
    out_e = jnp.einsum("ecf,efd->ecd", h, wo)

    # scatter back into the full-Ep layout (zeros elsewhere), gather the
    # per-token results, weight, and psum the partial outputs over model.
    full = jnp.zeros((ep, cap, d), out_e.dtype)
    full = jax.lax.dynamic_update_slice_in_dim(full, out_e, shard * e_loc, axis=0)
    gathered = full[flat_ids, slot_c]
    gathered = jnp.where(keep[:, None], gathered, 0)
    combined = (
        (gathered * flat_w[:, None].astype(gathered.dtype))
        .reshape(s_loc, m.experts_per_token, d)
        .sum(axis=1)
    )
    return jax.lax.psum(combined, "model")


def moe_apply_shardmap(p, x, cfg: ModelConfig, mesh):
    """Drop-in for the expert part of moe_apply (shared experts and the
    aux loss stay in the global-view caller). x: (B, T, D) global."""
    from .sharding import logical_to_spec, rules_for

    b, t, d = x.shape
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_model = mesh.shape["model"]
    rules = rules_for(mesh)
    # FSDP axes actually used for the experts' embed dim under the rules
    # (must match the parameters' resident sharding — no silent reshard).
    wg_spec = logical_to_spec(("experts", "embed", None), p["wg"].shape, mesh, rules)
    fsdp = wg_spec[1]
    fsdp_axes = () if fsdp is None else ((fsdp,) if isinstance(fsdp, str) else tuple(fsdp))

    x2 = x.reshape(b * t, d)
    fn = partial(_local_moe, cfg=cfg, n_model=n_model, fsdp_axes=fsdp_axes)
    out = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(dp_axes or None, None),           # tokens: batch-sharded
            P(None, None),                       # router: replicated
            P("model", fsdp, None),              # wg
            P("model", fsdp, None),              # wi
            P("model", None, fsdp),              # wo
        ),
        out_specs=P(dp_axes or None, None),
        check_vma=False,
    )(x2, p["router"], p["wg"], p["wi"], p["wo"])
    return out.reshape(b, t, d)
