"""Jitted jax kernels for the greedy baseline schedulers (paper §4.1-4.2).

``GreedyMinStorage`` and ``GreedyLeastUsed`` are the paper's cheap
baselines, yet after the D-Rex SC kernel landed their scalar loops were
the slowest decision paths at scale (GreedyMinStorage's fixed-point
search: ~180 ms/item at 500 nodes).  Both algorithms score *prefixes of
one sorted node order*, so the same masked-DP tensorization as
:mod:`repro.core.sc_kernel` applies: the per-prefix Poisson-binomial
parity frontier becomes one scan (the jax twin of
:meth:`ParityFrontier.upto_many` restricted to the ``start == 0`` row),
capacity checks become prefix-min tensors, and the whole program is
vmapped over a batch of items sharing a cluster snapshot — which is what
lets ``PlacementEngine.place_many`` drive both schedulers through
``place_batch`` with no engine special-casing.

Two scheduler-specific wrinkles keep the kernels bit-for-bit equivalent
to the scalar numpy oracles (``place_scalar``), which remain the
reference:

* **GreedyMinStorage's RNA regime.**  The scalar path asks
  :func:`min_parity_for_target` with ``method="auto"``: exact DP for
  mappings of at most ``_AUTO_EXACT_LIMIT`` (64) nodes, Hong's refined
  normal approximation above.  The RNA uses libm ``erf``/``exp`` whose
  jnp counterparts differ in ulps, so the kernel computes the exact-DP
  region in-jit and takes the RNA frontiers as a *host-computed input
  tensor* (:func:`rna_frontier_row`, which calls the very same scalar
  code path) — equivalence by construction instead of by reimplementation.

* **GreedyMinStorage's capacity filter.**  The fixed point over K maps
  chunks onto the fastest nodes *among those with room*
  (``free >= size/K``).  While every node of the bw-sorted prefix fits
  (the overwhelmingly common case — checked exactly via a prefix-min),
  the filtered mapping IS the prefix and the fixed point collapses to a
  closed form the kernel evaluates for every N at once.  Rows where the
  filter actually engages (capacity-tight clusters) are flagged ``slow``
  and finished on the host by the same per-N fixed point the scalar
  oracle runs (``GreedyMinStorage._fixed_point_row``); the final
  min-cost selection then merges both row kinds in scalar order.

``GreedyLeastUsed`` needs neither: its frontier is always the exact DP
(:class:`ParityFrontier`) and its mapping is always the free-desc prefix,
so the whole first-feasible-N scan runs in-jit.

D-Rex LB (§4.3) has its own kernel in :mod:`repro.core.lb_kernel`,
which resolves the balance penalty's summation-order problem by fixing
both paths to prefix-sum order and takes its parity frontiers as host
inputs from the oracle's own :class:`ParityFrontier` (the same
equivalence-by-construction move as this module's RNA rows; see that
module's docstring).

Everything runs in float64 on the host CPU device, under
:func:`repro.core.shapes.decision_scope` held tight around each kernel
call (availability targets with many nines need the full mantissa, and
the TPU's emulated float64 does not round as numpy does).  Pad planning goes through :mod:`repro.core.shapes`
(shared hysteresis-banded buckets + compile-cache census).

**Failure-domain constraints.**  Under ``PlacementConstraints`` both
greedy schedulers hand these kernels the cap-admitted subsequence of
their own sorted orders (``core.constraints.constrained_order``;
GreedyLeastUsed's ``SCAN_CAP`` slice additionally keeps per-domain
representatives via ``prefilter.domain_slice``).  Prefixes of an
admitted order are subsets of a cap-conforming set, so the in-kernel
scans are unchanged and greedy admission is WLOG for prefix-greedy
rules: any excluded node is dominated, under the scheduler's sort key,
by the cap's worth of same-domain nodes before it.  Unconstrained calls
pass identical arrays (bit-identical decisions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import shapes
from .reliability import _AUTO_EXACT_LIMIT, rna_parity_frontier

__all__ = [
    "least_used_batch",
    "min_storage_batch",
    "rna_frontier_row",
]


def rna_frontier_row(fail_sorted: np.ndarray, target: float, L: int) -> np.ndarray:
    """Host-side min-parity frontier for prefixes beyond the exact-DP limit.

    ``out[n]`` is the minimum parity for the length-``n`` prefix of
    ``fail_sorted`` (``-1`` infeasible), computed for
    ``n in (_AUTO_EXACT_LIMIT, L]`` exactly as the scalar oracle's
    :func:`min_parity_for_target` would under ``method="auto"`` (Hong's
    RNA with libm transcendentals; see
    :func:`repro.core.reliability.rna_parity_frontier`) — the kernel
    consumes this row verbatim for the approximation regime, keeping
    decisions bit-for-bit equal without reimplementing libm in XLA.
    ``BatchContext.rna_frontier`` memoizes rows across the items and
    commit groups of a batch.
    """
    out = np.full(L + 1, -1, dtype=np.int64)
    if L > _AUTO_EXACT_LIMIT:
        out[_AUTO_EXACT_LIMIT + 1 :] = rna_parity_frontier(
            fail_sorted, target, _AUTO_EXACT_LIMIT + 1, L
        )
    return out


def _prefix_frontier(probs, target, L, width, n_steps):
    """Min parity of every prefix of ``probs`` (one masked DP scan).

    Jax twin of ``ParityFrontier.upto_many(n_starts=1)`` — and of the
    exact branch of ``min_parity_for_target`` (full-width DP, cumsum
    CDF, first feasible index): ``out[i]`` is the min parity of the
    length-``i+1`` prefix, ``-1`` where infeasible, valid for steps
    ``i < n_steps``.  ``width`` bounds the tracked parity count (the
    full ``n_steps + 1`` for exactness).
    """

    def step(dp, i):
        p_i = probs[i]
        shifted = jnp.concatenate([jnp.zeros(1, dp.dtype), dp[:-1]])
        new_dp = dp * (1.0 - p_i) + shifted * p_i
        dp = jnp.where(i < L, new_dp, dp)
        cdf = jnp.cumsum(dp)
        feas = cdf >= target
        j = jnp.argmax(feas)
        ok = jnp.any(feas) & (j <= i) & (i < L)
        return dp, jnp.where(ok, j, -1).astype(jnp.int64)

    dp0 = jnp.zeros(width).at[0].set(1.0)
    _, mp = lax.scan(step, dp0, jnp.arange(n_steps))
    return mp  # (n_steps,) indexed by prefix length - 1


@functools.partial(jax.jit, static_argnums=(0,))
def _least_used_scores(
    L_pad,
    probs_b,     # (B, L_pad) per-item fail probs in free-desc order
    size_b,      # (B,)
    target_b,    # (B,)
    free,        # (L_pad,) free MB, free-desc order (pad -1)
    L,           # live-node count (traced; padding masked via L)
):
    """GreedyLeastUsed (Eq. 5): first N whose exact frontier admits
    ``K = N - max(1, P*) >= 2`` with the chunk fitting the prefix."""
    i_idx = jnp.arange(L_pad)
    n_arr = i_idx + 1

    def one(probs, size, target):
        mp = _prefix_frontier(probs, target, L, L_pad + 1, L_pad)
        p_star = jnp.maximum(1, mp)
        k = n_arr - p_star
        k_safe = jnp.maximum(k, 1)
        chunk = size / k_safe
        feasible = (
            (n_arr >= 2)
            & (n_arr <= L)
            & (mp >= 0)
            & (k >= 2)
            & (free >= chunk)  # free-desc prefix: min free is node N-1
        )
        idx = jnp.argmax(feasible)
        found = jnp.any(feasible)
        return (
            found,
            jnp.where(found, n_arr[idx], 0),
            jnp.where(found, k[idx], 0),
            jnp.where(found, p_star[idx], 0),
        )

    return jax.vmap(one)(probs_b, size_b, target_b)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _min_storage_scores(
    L_pad,
    EXACT,       # _AUTO_EXACT_LIMIT (static; mapping-size DP/RNA split)
    probs_b,     # (B, L_pad) per-item fail probs in write-bw-desc order
    size_b,      # (B,)
    target_b,    # (B,)
    rna_b,       # (B, L_pad + 1): host RNA frontier, indexed by N
    free_bw,     # (L_pad,) free MB, write-bw-desc order (pad -1)
    L,
):
    """GreedyMinStorage (Eq. 4): evaluate the per-N fixed point over K
    in closed form wherever the bw-sorted prefix fits the chunk.

    Returns per-(item, N) rows — ``valid``/``k``/``p``/``cost`` plus a
    ``slow`` flag for rows whose capacity filter engages (finished on
    the host; see module docstring).  Rows are indexed by ``N - 1``.
    """
    i_idx = jnp.arange(L_pad)
    n_arr = i_idx + 1
    fmin = lax.cummin(jnp.where(i_idx < L, free_bw, jnp.inf))

    def one(probs, size, target, rna):
        mp_exact = _prefix_frontier(
            probs, target, L, min(L_pad, EXACT) + 1, min(L_pad, EXACT)
        )
        mp_exact = jnp.concatenate(
            [mp_exact, jnp.full(L_pad - mp_exact.shape[0], -1, jnp.int64)]
        )
        # Frontier per prefix length N: exact DP for N <= EXACT, the
        # host-computed RNA row above (min_parity_for_target "auto").
        m_hat = jnp.where(n_arr <= EXACT, mp_exact, rna[1:])

        in_range = (n_arr >= 2) & (n_arr <= L)
        chunk0 = size / (n_arr - 1.0)        # first probe: K = N - 1
        fitcnt0 = jnp.sum(
            (free_bw[None, :] >= chunk0[:, None]) & (i_idx[None, :] < L),
            axis=1,
        )
        pfit0 = fmin >= chunk0               # whole prefix fits probe 1
        k1 = n_arr - m_hat                   # second probe: K = N - m_hat
        pfit1 = fmin >= size / jnp.maximum(k1, 1).astype(jnp.float64)

        # Probe 1 accepts immediately when min parity is already <= 1;
        # otherwise the fixed point re-probes at K = N - m_hat, where an
        # unchanged (still-prefix) mapping reproduces m_hat and accepts.
        acc1 = pfit0 & (m_hat >= 0) & (m_hat <= 1)
        deeper = pfit0 & (m_hat >= 2) & (k1 >= 1)
        acc2 = deeper & pfit1
        valid = in_range & (fitcnt0 >= n_arr) & (acc1 | acc2)
        slow = in_range & (fitcnt0 >= n_arr) & (
            (~pfit0) | (deeper & ~pfit1)
        )
        k = jnp.where(acc1, n_arr - 1, k1)
        p = jnp.where(acc1, 1, m_hat)
        cost = jnp.where(
            valid,
            (size / k.astype(jnp.float64)) * n_arr.astype(jnp.float64),
            jnp.inf,
        )
        return valid, slow, k, p, cost

    return jax.vmap(one)(probs_b, size_b, target_b, rna_b)


def _pad_batch(B: int, L: int):
    """Shared hysteresis-banded pads (see :mod:`repro.core.shapes`)."""
    return shapes.batch_pad(B), shapes.node_pad(L)


def _pad_to(a: np.ndarray, size: int, fill: float) -> np.ndarray:
    """``a`` extended to ``size`` with a neutral ``fill`` (shared padding
    idiom of both batch entry points)."""
    out = np.full(size, fill, dtype=np.float64)
    out[: a.shape[0]] = a
    return out


def least_used_batch(
    probs_mat: np.ndarray,   # (B, L) per-item fail probs, free-desc order
    sizes: np.ndarray,       # (B,)
    targets: np.ndarray,     # (B,)
    free_s: np.ndarray,      # (L,) free MB in the same order
):
    """GreedyLeastUsed decisions for a batch sharing one cluster snapshot.

    Returns ``(ok, n, k, p)`` length-B arrays: the first feasible prefix
    length and EC parameters per item (zeros where ``ok`` is False).
    Pure function of its arguments.
    """
    B, L = probs_mat.shape
    if L < 2 or B == 0:
        z = np.zeros(B, dtype=np.int64)
        return z.astype(bool), z, z, z
    B_pad, L_pad = _pad_batch(B, L)
    shapes.record_compile("least_used_kernel", (B_pad, L_pad))
    pm = np.zeros((B_pad, L_pad), dtype=np.float64)
    pm[:B, :L] = probs_mat
    with shapes.decision_scope():
        ok, n, k, p = _least_used_scores(
            L_pad,
            jnp.asarray(pm),
            jnp.asarray(_pad_to(sizes, B_pad, 1.0)),
            jnp.asarray(_pad_to(targets, B_pad, 0.5)),
            jnp.asarray(_pad_to(free_s, L_pad, -1.0)),
            np.int64(L),
        )
    shapes.record_device("least_used_kernel", ok)
    return (
        np.asarray(ok)[:B],
        np.asarray(n, dtype=np.int64)[:B],
        np.asarray(k, dtype=np.int64)[:B],
        np.asarray(p, dtype=np.int64)[:B],
    )


def min_storage_batch(
    probs_mat: np.ndarray,   # (B, L) per-item fail probs, write-bw-desc order
    sizes: np.ndarray,       # (B,)
    targets: np.ndarray,     # (B,)
    rna_rows: np.ndarray,    # (B, L + 1) host RNA frontier rows (by N)
    free_bw: np.ndarray,     # (L,) free MB in the same order
):
    """Per-(item, N) GreedyMinStorage scores for a batch sharing one
    cluster snapshot.

    Returns ``(valid, slow, k, p, cost)`` arrays of shape ``(B, L)`` with
    rows indexed by ``N - 1``; the caller finishes ``slow`` rows with the
    scalar fixed point and takes the min-cost row in ascending-N order
    (matching the oracle's strict-less tie-breaking).  Pure function.
    """
    B, L = probs_mat.shape
    if L < 2 or B == 0:
        shape = (B, max(L, 0))
        return (
            np.zeros(shape, dtype=bool),
            np.zeros(shape, dtype=bool),
            np.zeros(shape, dtype=np.int64),
            np.zeros(shape, dtype=np.int64),
            np.full(shape, np.inf),
        )
    B_pad, L_pad = _pad_batch(B, L)
    shapes.record_compile("min_storage_kernel", (B_pad, L_pad))
    pm = np.zeros((B_pad, L_pad), dtype=np.float64)
    pm[:B, :L] = probs_mat
    rna = np.full((B_pad, L_pad + 1), -1, dtype=np.int64)
    rna[:B, : L + 1] = rna_rows
    with shapes.decision_scope():
        valid, slow, k, p, cost = _min_storage_scores(
            L_pad,
            int(_AUTO_EXACT_LIMIT),
            jnp.asarray(pm),
            jnp.asarray(_pad_to(sizes, B_pad, 1.0)),
            jnp.asarray(_pad_to(targets, B_pad, 0.5)),
            jnp.asarray(rna),
            jnp.asarray(_pad_to(free_bw, L_pad, -1.0)),
            np.int64(L),
        )
    shapes.record_device("min_storage_kernel", valid)
    return (
        np.asarray(valid)[:B, :L],
        np.asarray(slow)[:B, :L],
        np.asarray(k, dtype=np.int64)[:B, :L],
        np.asarray(p, dtype=np.int64)[:B, :L],
        np.asarray(cost, dtype=np.float64)[:B, :L],
    )
