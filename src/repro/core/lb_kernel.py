"""Jitted jax kernel for D-Rex LB's (K, P) balance-penalty grid (Alg. 1).

D-Rex LB was the last hot-path scheduler still running a scalar numpy
loop: for each parity count P (ascending) it scores every data-chunk
count K by the balance penalty of mapping the item onto the
free-space-sorted prefix of K+P nodes, and stops at the smallest
feasible P (taking the best K there).  The kernel evaluates the full
(K, P) grid in two phases, neither of which materializes a (K, N)
float tensor:

1. **Smallest feasible P, O(L).**  At prefix length N the feasible K
   form the contiguous range ``[2, hi(N)]`` with
   ``hi(N) = N - max(1, mp(N))`` (the parity frontier bounds P from
   below), and since the chunk ``size/K`` shrinks as K grows, the range
   is nonempty iff its largest K fits — one exact float capacity
   compare per column.  The smallest feasible P at a valid column is
   ``max(1, mp(N))``; P* is a masked min over columns.
2. **Penalties on the P* diagonal, O(L) memory.**  The scalar loop
   evaluates the penalty ``sum_i |free_i - chunk - f_avg|`` for every K
   at the winning P (``N = K + P*``), so the kernel accumulates the
   per-K prefix sums with one O(K)-carry scan over node index,
   snapshotting each K row exactly at its own diagonal column.
   "Strictly smallest penalty, earliest K on ties" is a min plus an
   exact-equality masked min over K.

The whole program is vmapped over a batch of items sharing one cluster
snapshot (consumed by ``PlacementEngine.place_many`` through
``DRexLB.place_batch``).

**Exactness policy.**  Decisions are bit-for-bit equal to the scalar
oracle (``DRexLB.place_scalar``), with no fallback regimes, by keeping
every order-sensitive computation on the host:

* **Parity frontiers are a host input.**  ``mp_rows`` comes from the
  very :class:`reliability.ParityFrontier` the oracle consults (one DP
  per distinct ``(fail-probs, target)`` pair — batches overwhelmingly
  share it, and ``BatchContext.frontier`` memoizes across commit
  groups), the same equivalence-by-construction move the
  GreedyMinStorage kernel makes for its RNA rows.  Reimplementing the
  DP in XLA was both slower (a serial ``lax.scan`` per item dominated
  the kernel's runtime) and riskier (XLA's ``cumsum`` lowering
  re-associates, which can flip a threshold compare in ulp-tight
  cases — measurably: ``jnp.cumsum`` != ``np.cumsum`` bitwise on CPU).
* **Summation order is fixed on both paths.**  A float sum depends on
  its grouping, and numpy's pairwise ``.sum()`` cannot be cheaply
  reproduced in XLA, so the penalty sums are defined — on *both*
  paths — in plain left-to-right prefix-sum order: the oracle
  accumulates with ``np.cumsum`` (sequential by construction), the
  kernel with an explicit ``lax.scan`` carry (never ``jnp.cumsum``).
  The remaining order-sensitive global terms — ``f_avg`` (a numpy
  pairwise mean) and the out-of-mapping suffix penalties (a reversed
  ``np.cumsum``) — are host inputs too.

Equivalence across normal, capacity-tight and low-reliability regimes
is pinned by tests/test_lb_vectorized.py.

Everything runs in float64 on the host CPU device, under
:func:`repro.core.shapes.decision_scope` held tight around the kernel
call (many-nines availability targets need the full mantissa, and the
TPU's emulated float64 does not round as numpy does).  Pad planning goes through :mod:`repro.core.shapes` (shared
hysteresis-banded buckets + compile-cache census).

**Failure-domain constraints.**  Under ``PlacementConstraints`` the
free-descending node order handed to the grid is the cap-admitted
subsequence (``core.constraints.constrained_order``): every (K, P)
prefix of it is a subset of a cap-conforming set, so the grid math is
untouched.  Because the frontier's prefix rows must remain *plain*
prefixes of the scored order, the top-M pre-filter is bypassed (not
domain-sliced) whenever its prefix cannot span a required spread width
— correctness first, the filter is only ever a fast path.
Unconstrained calls pass the identical arrays as before (bit-identical
decisions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import shapes

__all__ = ["lb_batch"]


@functools.partial(jax.jit, static_argnums=(0,))
def _lb_scores(
    L_pad,
    mp_b,        # (B, L_pad) host frontier: min parity per prefix length
    size_b,      # (B,)
    free,        # (L_pad,) free MB, free-desc order (pad -1)
    suffix,      # (L_pad + 1,) host suffix penalties by n (pad 0)
    f_avg,       # scalar: mean free over live nodes (host-computed)
    L,           # live-node count (traced; padding masked via L)
):
    """D-Rex LB (Alg. 1) for a batch: per item, the winning (K, P).

    See the module docstring for the two-phase structure and the
    exactness policy.  ``mp_b[row, n-1]`` is the min parity of the
    length-``n`` free-desc prefix (``-1`` infeasible), straight from
    the oracle's :class:`ParityFrontier`.
    """
    k_arr = jnp.arange(L_pad) + 2
    n_row = jnp.arange(L_pad) + 1
    i_idx = jnp.arange(L_pad)
    big = jnp.int64(L_pad + 2)

    def one(mp, size):
        chunk = size / k_arr.astype(jnp.float64)
        # ---- phase 1: smallest feasible P (line 22), O(L)
        hi = jnp.where(mp >= 0, n_row - jnp.maximum(1, mp), 0)
        col_ok = (
            (n_row <= L)
            & (hi >= 2)
            # same float predicate the oracle tests: free[n-1] >= size/K
            & (free >= size / jnp.maximum(hi, 1).astype(jnp.float64))
        )
        p_star = jnp.min(jnp.where(col_ok, jnp.maximum(1, mp), big))
        ok = p_star < big
        # ---- phase 2: penalties on the N = K + P* diagonal
        n_diag = jnp.clip(k_arr + p_star, 2, L_pad)
        mp_d = mp[n_diag - 1]
        feas_d = (
            ok
            & (k_arr + p_star <= L)
            & (mp_d >= 0)
            & (mp_d <= p_star)
            & (free[n_diag - 1] >= chunk)
        )

        def body(carry, x):
            run, acc = carry
            i, f_i = x
            run = run + jnp.abs(f_i - chunk - f_avg)
            acc = jnp.where(i == n_diag - 1, run, acc)
            return (run, acc), None

        (_, acc), _ = lax.scan(
            body,
            (jnp.zeros(L_pad), jnp.zeros(L_pad)),
            (i_idx, free),
        )
        # lines 10-15: in-mapping prefix sum + precomputed suffix term.
        bp = jnp.where(feas_d, acc + suffix[n_diag], jnp.inf)
        bv = jnp.min(bp)
        k_star = jnp.min(jnp.where(feas_d & (bp == bv), k_arr, big))
        return (
            ok,
            jnp.where(ok, k_star, 0),
            jnp.where(ok, p_star, 0),
        )

    return jax.vmap(one)(mp_b, size_b)


def _pad_to(a: np.ndarray, size: int, fill: float) -> np.ndarray:
    out = np.full(size, fill, dtype=np.float64)
    out[: a.shape[0]] = a
    return out


def lb_batch(
    mp_rows: np.ndarray,     # (B, L) host ParityFrontier rows, by n - 1
    sizes: np.ndarray,       # (B,)
    free_s: np.ndarray,      # (L,) free MB, free-desc order
    f_avg: float,            # host-computed mean free over live nodes
    suffix: np.ndarray,      # (L + 1,) host-computed suffix penalties
):
    """D-Rex LB decisions for a batch sharing one cluster snapshot.

    Returns ``(ok, k, p)`` length-B arrays: the winning EC parameters
    per item (zeros where ``ok`` is False — genuinely infeasible, since
    the host frontier rows are exact at every width; the mapping is
    always the free-desc prefix of ``k + p`` nodes).  Pure function of
    its arguments.
    """
    B, L = mp_rows.shape
    if L < 3 or B == 0:
        z = np.zeros(B, dtype=np.int64)
        return z.astype(bool), z, z
    L_pad = shapes.node_pad(L)
    B_pad = shapes.batch_pad(B)
    shapes.record_compile("lb_kernel", (B_pad, L_pad))
    mp = np.full((B_pad, L_pad), -1, dtype=np.int64)
    mp[:B, :L] = mp_rows
    suf = np.zeros(L_pad + 1, dtype=np.float64)
    suf[: L + 1] = suffix
    with shapes.decision_scope():
        ok, k, p = _lb_scores(
            L_pad,
            jnp.asarray(mp),
            jnp.asarray(_pad_to(sizes, B_pad, 1.0)),
            jnp.asarray(_pad_to(free_s, L_pad, -1.0)),
            jnp.asarray(suf),
            jnp.asarray(np.float64(f_avg)),
            np.int64(L),
        )
    shapes.record_device("lb_kernel", ok)
    return (
        np.asarray(ok)[:B],
        np.asarray(k, dtype=np.int64)[:B],
        np.asarray(p, dtype=np.int64)[:B],
    )
