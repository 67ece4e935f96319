"""Plain reference of D-Rex SC placement (arXiv:2506.02026 §4.4, Alg. 2).

A straightforward numpy statement of the decision function, written
from the paper and independent of the code under test: it imports
nothing of the program.  One item at a time, in arrival order:

1. live nodes sorted by free space, descending (ties by node id);
2. candidate mappings are contiguous windows ``[s, s + n)`` of that
   order, start-major, at most ``max_mappings`` of them;
3. per window the least parity P whose Poisson-binomial availability
   (Eq. 2) meets the item's reliability target, P >= 1, K = n - P;
4. objectives: duration (transfer over the slowest node plus the linear
   coding-time model), storage ``n * size / K`` and the saturation of
   the whole repository after the write;
5. the Pareto front of those three is scored by relative progress,
   weighted by the system's saturation; the first best wins;
6. a placed item adds ``size / K`` MB to each of its nodes.

``dtype`` is the precision of every quantity above; float64 is what the
configuration states, float32 is the control.
"""

from __future__ import annotations

import math

import numpy as np

DAYS_PER_YEAR = 365.25


class Cluster:
    """The reference's own copy of the nodes' state."""

    def __init__(self, capacity_mb, used_mb, write_bw, read_bw, afr, dtype=np.float64):
        self.dtype = np.dtype(dtype)
        as_ = lambda a: np.array(a, dtype=self.dtype)  # noqa: E731
        self.capacity = as_(capacity_mb)
        self.used = as_(used_mb)
        self.write_bw = as_(write_bw)
        self.read_bw = as_(read_bw)
        self.afr = as_(afr)
        self.alive = np.ones(self.capacity.shape[0], dtype=bool)

    def commit(self, node_ids, chunk_mb) -> None:
        self.used[np.asarray(node_ids)] += self.dtype.type(chunk_mb)


def saturation(x, cap, smin, n_live):
    """Exponential through (smin, 1/L) and (capacity, 1), clipped to [0, 1]."""
    one = x.dtype.type(1.0)
    span = np.maximum(cap - smin, x.dtype.type(1e-9))
    u = np.clip((x - smin) / span, 0.0, 1.0).astype(x.dtype)
    big_l = max(2, n_live)
    inv_l = one / x.dtype.type(big_l)
    return np.clip(inv_l * np.exp(x.dtype.type(math.log(big_l)) * u), 0.0, 1.0).astype(x.dtype)


def min_parity_per_prefix(fail_probs, target, nmax):
    """``out[n - 1]``: least P in [0, n - 1] with Pr[at most P of the first
    n nodes fail] >= target, or -1; for n = 1..nmax."""
    dt = fail_probs.dtype
    dp = np.zeros(nmax + 1, dtype=dt)
    dp[0] = 1.0
    out = np.full(nmax, -1, dtype=np.int64)
    j = 0
    for i in range(nmax):
        pi = fail_probs[i]
        dp[1 : i + 2] = dp[1 : i + 2] * (dt.type(1.0) - pi) + dp[: i + 1] * pi
        dp[0] *= dt.type(1.0) - pi
        # Adding a node only lowers the CDF at fixed P: P never decreases.
        cdf = dt.type(dp[: j + 1].sum())
        while cdf < target and j <= i:
            j += 1
            cdf = dt.type(cdf + dp[j])
        if j <= i:
            out[i] = j
    return out


def _pareto_keep(obj):
    m = obj.shape[0]
    le = np.ones((m, m), dtype=bool)
    lt = np.zeros((m, m), dtype=bool)
    for col in range(obj.shape[1]):
        c = obj[:, col]
        le &= c[None, :] <= c[:, None]
        lt |= c[None, :] < c[:, None]
    keep = ~np.any(le & lt, axis=1)
    return keep if keep.any() else np.ones(m, dtype=bool)


def _progress(v):
    lo, hi = v.min(), v.max()
    if float(hi) - float(lo) <= 1e-12:
        return np.zeros_like(v)
    return (hi - v) / (hi - lo)


def decide(cl: Cluster, size_mb, target, delta_t_days, smin, scheduler: dict):
    """One D-Rex SC decision on ``cl``: ``(k, p, node_ids)`` or None."""
    t = cl.dtype.type
    tm = scheduler["time_model"]
    live = np.nonzero(cl.alive)[0]
    free = cl.capacity - cl.used
    order = live[np.argsort(-free[live], kind="stable")]
    big_l = len(order)
    if big_l < 2:
        return None
    fp = (-np.expm1(-cl.afr * t(delta_t_days / DAYS_PER_YEAR))).astype(cl.dtype)[order]
    free_s, wb_s, rb_s = free[order], cl.write_bw[order], cl.read_bw[order]
    used_s, cap_s = cl.used[order], cl.capacity[order]
    size, smin, target = t(size_mb), t(smin), t(target)
    f_base = t(saturation(cl.used[live], cl.capacity[live], smin, big_l).sum())
    rows = []
    budget = int(scheduler["max_mappings"])
    for s in range(big_l - 1):
        if budget <= 0:
            break
        n_wins = min(big_l - s - 1, budget)
        budget -= n_wins
        nmax = n_wins + 1
        mp = min_parity_per_prefix(fp[s : s + nmax], target, nmax)[1:nmax]
        n = np.arange(2, nmax + 1)
        p = np.maximum(1, mp)
        k = n - p
        ok = (mp >= 0) & (k >= 1)
        k_safe = np.where(ok, k, 1)
        chunk = (size / k_safe.astype(cl.dtype)).astype(cl.dtype)
        ok &= free_s[s + n - 1] >= chunk
        if not ok.any():
            continue
        wb = np.minimum.accumulate(wb_s[s : s + nmax])[n - 1]
        rb = np.minimum.accumulate(rb_s[s : s + nmax])[n - 1]
        enc = np.where(k_safe == 1, t(tm["e0"]),
                       t(tm["e0"]) + t(tm["e_byte"]) * size
                       + t(tm["e_mult"]) * (n - k_safe).astype(cl.dtype) * size)
        dec = np.where(k_safe == 1, t(tm["d0"]),
                       t(tm["d0"]) + t(tm["d_byte"]) * size
                       + t(tm["d_mult"]) * k_safe.astype(cl.dtype) * size)
        duration = chunk / wb + chunk / rb + enc + dec
        storage = chunk * n.astype(cl.dtype)
        u, c = used_s[s : s + nmax], cap_s[s : s + nmax]
        delta = saturation(u[None, :] + chunk[:, None], c[None, :], smin, big_l) \
            - saturation(u, c, smin, big_l)[None, :]
        inside = np.arange(nmax)[None, :] < n[:, None]
        sat = f_base + (delta * inside).sum(axis=1)
        for i in np.nonzero(ok)[0]:
            rows.append((s, int(n[i]), int(k[i]), int(p[i]),
                         duration[i], storage[i], sat[i]))
    if not rows:
        return None
    obj = np.array([r[4:] for r in rows], dtype=cl.dtype)
    keep = np.nonzero(_pareto_keep(obj))[0]
    sys_sat = saturation(np.array([cl.used[live].sum()], dtype=cl.dtype),
                         np.array([cl.capacity[live].sum()], dtype=cl.dtype),
                         smin, big_l)[0]
    front = obj[keep]
    score = (t(1.0) - sys_sat) * _progress(front[:, 0]) \
        + (_progress(front[:, 1]) + _progress(front[:, 2])) / t(2.0)
    s, n, k, p = rows[keep[int(np.argmax(score))]][:4]
    return k, p, tuple(int(x) for x in order[s : s + n])
