"""Jitted jax kernel for D-Rex SC's (starts x window-lengths) scoring.

``DRexSC`` enumerates up to ``MAX_MAPPINGS`` contiguous windows of the
free-space-sorted live nodes and scores each on (duration, storage,
saturation) before a Pareto-front selection (Alg. 2).  The scalar numpy
path (:meth:`DRexSC.place_scalar`) remains the reference oracle; this
module computes the same decision as one jitted kernel over a padded
(starts x window-lengths) tensor:

* the per-start Poisson-binomial parity frontiers become one masked DP
  over *all* suffixes at once (a ``(starts, prefix-length)`` tensor, the
  jax twin of :meth:`ParityFrontier.upto_many`);
* capacity checks and bandwidth bottlenecks are prefix-min tensors;
* the enumerated windows (at most ``budget`` of them, in the scalar
  path's start-major order) are compacted to a fixed-width candidate
  axis, scored, and Pareto-masked in-kernel;
* the whole thing is vmapped over a batch of items sharing one cluster
  snapshot, which is what lets ``PlacementEngine.place_many`` score a
  queue of items in a single call.

Everything runs in float64 on the host CPU device, under
:func:`repro.core.shapes.decision_scope` held tight around the kernel
call (the DP discriminates seven-nines availability targets, which
float32 cannot represent, and the TPU's emulated float64 does not round
as numpy does), so the kernel is decision-equivalent to the numpy
oracle; tests/test_sc_vectorized.py enforces this bit-for-bit on pinned
traces.  The scope is thread-local, so coding launches on other threads
never see 64-bit types.

**Failure-domain constraints.**  Under ``PlacementConstraints`` the
candidate-node axis arrives already masked: ``DRexSC`` feeds the kernel
the cap-admitted subsequence of its free-descending order
(``core.constraints.constrained_order``, with per-domain
representatives kept by ``prefilter.domain_slice``), so every
enumerated window is a subset of a cap-conforming set and the in-kernel
math — including the saturation scale, which stays anchored to the
*cluster-wide* live count via ``n_live`` — is unchanged.  Unconstrained
calls pass the identical arrays as before, keeping decisions
bit-identical to the pinned goldens.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import shapes

__all__ = ["score_windows_batch"]


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _score_windows(
    S_pad,
    L_pad,
    budget,
    probs_b,     # (B, L_pad) per-item fail probs in free-desc order
    size_b,      # (B,)
    target_b,    # (B,)
    smin_b,      # (B,) running smallest-item anchor per item
    fbase_b,     # (B,) sum of per-node saturation over live nodes
    ssat_b,      # (B,) system saturation scalar
    free,        # (L_pad,) shared sorted cluster snapshot ------------
    wb,
    rb,
    used,
    cap,
    L,           # live-node count (traced; padding is masked via L)
    inv_l,       # 1 / max(2, L)
    log_l,       # log(max(2, L))
    tm,          # (6,) ECTimeModel params e0,e_byte,e_mult,d0,d_byte,d_mult
):
    K_c = min(budget, S_pad * L_pad)  # enumerated windows <= budget
    s_idx = jnp.arange(S_pad)
    i_idx = jnp.arange(L_pad)
    act2 = i_idx[None, :] >= s_idx[:, None]  # (S, L): end >= start

    # Bottleneck bandwidth of window [s..i] is a running min over the
    # suffix starting at s (exact: min has no rounding).
    wb_min = lax.cummin(jnp.where(act2, wb[None, :], jnp.inf), axis=1)
    rb_min = lax.cummin(jnp.where(act2, rb[None, :], jnp.inf), axis=1)

    # Scalar enumeration order and budget: start s contributes
    # min(L-1-s, remaining budget) windows, starts in ascending order.
    w_full = jnp.clip(L - 1 - s_idx, 0, None)
    cum_before = jnp.concatenate(
        [jnp.zeros(1, w_full.dtype), jnp.cumsum(w_full)[:-1]]
    )
    allowed = jnp.clip(budget - cum_before, 0, w_full)
    win_idx = i_idx[None, :] - s_idx[:, None] - 1  # 0 <=> window n=2
    in_budget = (win_idx >= 0) & (win_idx < allowed[:, None])
    in_budget &= i_idx[None, :] <= L - 1

    # Compact the (S, L) window grid to a fixed candidate axis in the
    # scalar path's (start-major, length-minor) order: a stable sort
    # moves the <= budget enumerated windows to the front unpermuted.
    flat_order = jnp.argsort(
        jnp.where(in_budget.ravel(), 0, 1).astype(jnp.int32)
    )[:K_c]
    s_w = flat_order // L_pad
    i_w = flat_order % L_pad
    enumerated = in_budget.ravel()[flat_order]
    n_w = i_w - s_w + 1

    e0, e_byte, e_mult, d0, d_byte, d_mult = (
        tm[0], tm[1], tm[2], tm[3], tm[4], tm[5]
    )

    def saturation(x, c, smin):
        # Mirror of algorithms.saturation_score (elementwise, f64).
        span = jnp.maximum(c - smin, 1e-9)
        u = jnp.clip((x - smin) / span, 0.0, 1.0)
        return jnp.clip(inv_l * jnp.exp(log_l * u), 0.0, 1.0)

    def one(probs, size, target, smin, f_base_sum, sys_sat):
        # ---- parity frontier of every suffix, one masked DP --------
        def step(dp, i):
            p_i = probs[i]
            shifted = jnp.concatenate(
                [jnp.zeros((S_pad, 1), dp.dtype), dp[:, :-1]], axis=1
            )
            new_dp = dp * (1.0 - p_i) + shifted * p_i
            dp = jnp.where((i >= s_idx)[:, None], new_dp, dp)
            cdf = jnp.cumsum(dp, axis=1)
            feas = cdf >= target
            j = jnp.argmax(feas, axis=1)
            n_len = i - s_idx + 1
            ok = jnp.any(feas, axis=1) & (j <= n_len - 1)
            return dp, jnp.where(ok, j, -1)

        dp0 = jnp.zeros((S_pad, L_pad + 1)).at[:, 0].set(1.0)
        _, cols = lax.scan(step, dp0, i_idx)
        mp = cols.T[s_w, i_w]  # (K_c,) min parity per window

        p_star = jnp.maximum(1, mp)
        k = n_w - p_star
        valid = enumerated & (mp >= 0) & (k >= 1)
        k_safe = jnp.where(valid, k, 1)
        chunk = size / k_safe
        # Mapping is free-desc sorted: the window min free is its
        # last node (index i).
        valid &= free[i_w] >= chunk

        enc = jnp.where(
            k_safe == 1,
            e0,
            e0 + e_byte * size + e_mult * (n_w - k_safe) * size,
        )
        dec = jnp.where(
            k_safe == 1, d0, d0 + d_byte * size + d_mult * k_safe * size
        )
        duration = (
            chunk / wb_min[s_w, i_w] + chunk / rb_min[s_w, i_w] + enc + dec
        )
        storage = chunk * n_w

        # Saturation objective: base sum over all live nodes plus the
        # delta of the window's nodes at projected occupancy.
        in_win = (i_idx[None, :] >= s_w[:, None]) & (
            i_idx[None, :] <= i_w[:, None]
        )
        delta = (
            (
                saturation(used[None, :] + chunk[:, None], cap[None, :], smin)
                - saturation(used, cap, smin)[None, :]
            )
            * in_win
        ).sum(axis=1)
        sat_obj = f_base_sum + delta

        # ---- Pareto front + relative-progress scoring (lines 11-17)
        dur_f = jnp.where(valid, duration, jnp.inf)
        sto_f = jnp.where(valid, storage, jnp.inf)
        sat_f = jnp.where(valid, sat_obj, jnp.inf)
        le = jnp.ones((K_c, K_c), bool)
        lt = jnp.zeros((K_c, K_c), bool)
        for c in (dur_f, sto_f, sat_f):
            le &= c[None, :] <= c[:, None]
            lt |= c[None, :] < c[:, None]
        front = ~jnp.any(le & lt, axis=1) & valid

        def progress(v):
            lo = jnp.min(jnp.where(front, v, jnp.inf))
            hi = jnp.max(jnp.where(front, v, -jnp.inf))
            return jnp.where(hi - lo <= 1e-12, 0.0, (hi - v) / (hi - lo))

        score = (1.0 - sys_sat) * progress(dur_f) + (
            progress(sto_f) + progress(sat_f)
        ) / 2.0
        best = jnp.argmax(jnp.where(front, score, -jnp.inf))
        bp = jnp.maximum(1, mp[best])
        return (
            jnp.any(valid),
            s_w[best],
            n_w[best],
            n_w[best] - bp,
            bp,
        )

    return jax.vmap(one)(
        probs_b, size_b, target_b, smin_b, fbase_b, ssat_b
    )


def _shape_plan(L: int, budget: int) -> tuple[int, int]:
    """Static (S_pad, L_pad) for a live-node count: L padded through the
    shared hysteresis-banded buckets (:mod:`repro.core.shapes`), starts
    covering every budgeted window."""
    L_pad = shapes.node_pad(L)
    if L_pad <= 64:
        return L_pad - 1, L_pad  # every start can matter; keep stable
    w = L - 1 - np.arange(L - 1)
    consider = min(int(w.sum()), budget)
    s_real = int(np.searchsorted(np.cumsum(w), consider) + 1)
    return min(L_pad - 1, shapes.start_pad(s_real)), L_pad


def score_windows_batch(
    probs_mat: np.ndarray,   # (B, L) per-item fail probs, free-desc order
    sizes: np.ndarray,       # (B,)
    targets: np.ndarray,     # (B,)
    smins: np.ndarray,       # (B,)
    fbase: np.ndarray,       # (B,)
    ssat: np.ndarray,        # (B,)
    free_s: np.ndarray,      # (L,) shared sorted cluster snapshot
    wb_s: np.ndarray,
    rb_s: np.ndarray,
    used_s: np.ndarray,
    cap_s: np.ndarray,
    budget: int,
    tm_params: tuple,        # (e0, e_byte, e_mult, d0, d_byte, d_mult)
    n_live: int | None = None,
):
    """Score every item's candidate windows against one shared snapshot.

    Returns ``(ok, s, n, k, p)`` int64 arrays of length B: the winning
    window start/length and EC parameters per item (undefined where
    ``ok`` is False).  Pure function of its arguments — callers own all
    cluster/scheduler state.

    ``n_live`` is the true live-node count when the node arrays are a
    top-M pre-filtered slice (see :mod:`repro.core.prefilter`): the
    ``1/L`` / ``log L`` saturation scale is an Alg. 2 property of the
    *cluster*, not of the slice handed to the kernel, so it must come
    from the caller.  Defaults to the array length (unfiltered call).
    """
    B, L = probs_mat.shape
    if L < 2 or B == 0:
        z = np.zeros(B, dtype=np.int64)
        return z.astype(bool), z, z, z, z
    S_pad, L_pad = _shape_plan(L, budget)
    B_pad = shapes.batch_pad(B)
    shapes.record_compile("sc_kernel", (B_pad, S_pad, L_pad, int(budget)))

    def pad_nodes(a, fill):
        out = np.full(L_pad, fill, dtype=np.float64)
        out[:L] = a
        return out

    pm = np.zeros((B_pad, L_pad), dtype=np.float64)
    pm[:B, :L] = probs_mat

    def pad_items(a, fill):
        out = np.full(B_pad, fill, dtype=np.float64)
        out[:B] = a
        return out

    l_eff = max(2, L if n_live is None else int(n_live))
    with shapes.decision_scope():
        ok, s, n, k, p = _score_windows(
            S_pad,
            L_pad,
            int(budget),
            jnp.asarray(pm),
            jnp.asarray(pad_items(sizes, 1.0)),
            jnp.asarray(pad_items(targets, 0.5)),
            jnp.asarray(pad_items(smins, 1.0)),
            jnp.asarray(pad_items(fbase, 0.0)),
            jnp.asarray(pad_items(ssat, 0.0)),
            jnp.asarray(pad_nodes(free_s, -1.0)),
            jnp.asarray(pad_nodes(wb_s, 1.0)),
            jnp.asarray(pad_nodes(rb_s, 1.0)),
            jnp.asarray(pad_nodes(used_s, 0.0)),
            jnp.asarray(pad_nodes(cap_s, 1.0)),
            np.int64(L),
            np.float64(1.0 / l_eff),
            np.float64(math.log(l_eff)),
            jnp.asarray(np.asarray(tm_params, dtype=np.float64)),
        )
    shapes.record_device("sc_kernel", ok)
    return (
        np.asarray(ok)[:B],
        np.asarray(s, dtype=np.int64)[:B],
        np.asarray(n, dtype=np.int64)[:B],
        np.asarray(k, dtype=np.int64)[:B],
        np.asarray(p, dtype=np.int64)[:B],
    )
