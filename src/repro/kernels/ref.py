"""Pure-jnp oracles: the semantics the fused scan kernels must match.

Kernels must match them exactly (integer outputs) or to float tolerance
(distances).  Shapes:

  hash_encode_ref : (n, d) x (d, beta) -> (n, beta) int32 bucket codes
                    (also the hash the build and ``encode_queries`` run)
  freq_level_ref  : (n, beta) codes x (Q, beta) query codes -> (Q, n) int32
                    first level j (0..n_levels) at which the point is
                    *frequent* for the query (collision count >= mu at
                    level-c^j buckets); n_levels + 1 if never frequent.
  count_level_ref : collision counts at one fixed level (faithful C2LSH)
  weighted_lp_ref : (Q, d) x (n, d) -> (Q, n) distances under weight W

The fused-query oracles (``fused_query_hist_ref`` / ``fused_query_scores_ref``)
define the semantics of one fused block step — first-frequent level, weighted
distance, good-level histogramming and stop-mask scoring in one composite,
built from the stage oracles above and ``per_query_dist``.  They are also
the *serving* fused path off-TPU, so they take a block as the group state
stores it, point axis minor: codes (beta, B) and vectors (d, B).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = [
    "hash_encode_ref",
    "freq_level_ref",
    "count_level_ref",
    "weighted_lp_ref",
    "log_c",
    "per_query_l2",
    "per_query_lp",
    "per_query_dist",
    "fused_query_hist_ref",
    "fused_query_scores_ref",
]

# The p = 2 expansion cancels large terms; its contractions run in full
# f32 on every backend (a TPU's default rounds f32 operands through bf16),
# matching the fused kernel's.
_F32 = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=())
def hash_encode_ref(points, proj, b_int, b_frac, weight, width):
    """floor((a . (W o x))/w + b_frac) + b_int, exact-int split of b*."""
    x = points.astype(jnp.float32) * weight.astype(jnp.float32)
    u = (x @ proj.astype(jnp.float32)) / width + b_frac
    return jnp.floor(u).astype(jnp.int32) + b_int.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("c", "n_levels", "unroll"))
def _freq_level_ref(codes_p, codes_q, mu, beta_q, c: int, n_levels: int,
                    unroll: bool = False):
    never = jnp.int32(n_levels + 1)
    out = jnp.full((codes_q.shape[0], codes_p.shape[0]), never, jnp.int32)
    a = codes_p.astype(jnp.int32)  # (n, beta)
    b = codes_q.astype(jnp.int32)  # (Q, beta)
    lane = jnp.arange(a.shape[1], dtype=jnp.int32)
    lane_ok = (lane[None, :] < beta_q[:, None]).astype(jnp.int32)  # (Q, beta)

    def body(j, carry):
        a, b, out = carry
        cnt = jnp.sum(
            (b[:, None, :] == a[None, :, :]).astype(jnp.int32)
            * lane_ok[:, None, :],
            axis=-1,
        )  # (Q, n)
        hit = (cnt >= mu[:, None]) & (out == never)
        out = jnp.where(hit, jnp.int32(j), out)
        return (jnp.floor_divide(a, c), jnp.floor_divide(b, c), out)

    carry = (a, b, out)
    if unroll:  # analysis: cost_analysis counts loop bodies once
        for j in range(n_levels + 1):
            carry = body(j, carry)
        return carry[2]
    a, b, out = jax.lax.fori_loop(0, n_levels + 1, body, carry)
    return out


def freq_level_ref(codes_p, codes_q, mu, c: int, n_levels: int, beta_q=None,
                   unroll: bool = False):
    """First frequent level per (query, point); fuses all C2LSH radii.

    ``mu`` may be a scalar or (Q,); ``beta_q`` optionally limits each query
    to its first beta_q hash tables (WLSH per-member beta_{W_i} semantics;
    default = all tables).
    """
    q = codes_q.shape[0]
    mu_arr = jnp.broadcast_to(jnp.asarray(mu, jnp.int32), (q,))
    if beta_q is None:
        beta_q = jnp.full((q,), codes_p.shape[1], jnp.int32)
    beta_arr = jnp.broadcast_to(jnp.asarray(beta_q, jnp.int32), (q,))
    return _freq_level_ref(codes_p, codes_q, mu_arr, beta_arr, int(c),
                           int(n_levels), unroll=unroll)


@functools.partial(jax.jit, static_argnames=("c", "level"))
def count_level_ref(codes_p, codes_q, c: int, level: int):
    """Collision counts at level c**level (paper-faithful single radius)."""
    l = c**level
    a = jnp.floor_divide(codes_p.astype(jnp.int32), l)
    b = jnp.floor_divide(codes_q.astype(jnp.int32), l)
    return jnp.sum((b[:, None, :] == a[None, :, :]).astype(jnp.int32), axis=-1)


@functools.partial(jax.jit, static_argnames=("p",))
def weighted_lp_ref(queries, points, weight, p: float):
    """(Q, n) weighted l_p distances, f32."""
    qw = queries.astype(jnp.float32) * weight
    pw = points.astype(jnp.float32) * weight
    if abs(p - 2.0) < 1e-9:
        qq = jnp.sum(qw * qw, axis=-1)
        pp = jnp.sum(pw * pw, axis=-1)
        cross = jnp.matmul(qw, pw.T, precision=_F32)
        d2 = qq[:, None] + pp[None, :] - 2.0 * cross
        return jnp.sqrt(jnp.maximum(d2, 0.0))
    diff = jnp.abs(qw[:, None, :] - pw[None, :, :])
    if abs(p - 1.0) < 1e-9:
        return jnp.sum(diff, axis=-1)
    return jnp.sum(diff**p, axis=-1) ** (1.0 / p)


# --------------------------------------------------- fused query-step oracles


def log_c(x, c: int):
    """log base c, the virtual-rehashing level scale."""
    return jnp.log(x) / math.log(c)


def per_query_l2(q, w, pts):
    """(Q, B) weighted l2 with per-query weights, via two matmuls (MXU).

    ``pts`` is (d, B), point axis minor.
    """
    w2 = w * w
    qw2 = jnp.sum(w2 * q * q, axis=-1)  # (Q,)
    cross = jnp.matmul(w2 * q, pts, precision=_F32)  # (Q, B)
    onorm = jnp.matmul(w2, pts * pts, precision=_F32)  # (Q, B)
    d2 = qw2[:, None] - 2.0 * cross + onorm
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def per_query_lp(q, w, pts, p: float):
    """(Q, B) weighted l_p (p != 2) with per-query weights, elementwise.

    ``pts`` is (d, B); the sum runs over d, the kernel's sublane axis.
    """
    diff = jnp.abs((q[:, :, None] - pts[None, :, :]) * w[:, :, None])
    if abs(p - 1.0) < 1e-9:
        return jnp.sum(diff, axis=1)
    return jnp.sum(diff**p, axis=1) ** (1.0 / p)


def per_query_dist(q, w, pts, p: float):
    """Per-query-weight distance: the l2 expansion at p = 2, else l_p.

    Tests that rebuild the composite from its stages call this very
    function on the same shapes, under jit — f32 gemms are
    shape-sensitive in the last ulp.
    """
    if abs(p - 2.0) < 1e-9:
        return per_query_l2(q, w, pts)
    return per_query_lp(q, w, pts, p)


def _fused_lf(codes_b, codes_q, mu, beta_q, row_ok, c, n_levels, unroll):
    """(Q, B) first-frequent level with excluded rows forced to L + 2.

    ``codes_b`` is (beta, B), as the state stores it.  Excluded rows
    (rows at/after the streaming ``n_valid`` watermark) get the sentinel
    ``n_levels + 2`` — past every histogram bin the stop logic reads
    (0..n_levels) and past every reachable stop level, so they vanish
    from both passes.
    """
    lf = freq_level_ref(codes_b.T, codes_q, mu, c, n_levels, beta_q,
                        unroll=unroll)
    return jnp.where(row_ok[None, :], lf, jnp.int32(n_levels + 2))


@functools.partial(
    jax.jit, static_argnames=("c", "n_levels", "p", "unroll")
)
def fused_query_hist_ref(codes_b, points_b, codes_q, queries, q_weight, mu,
                         beta_q, r_min, row_ok, c: int, n_levels: int,
                         p: float, unroll: bool = False):
    """Pass-1 fused block step: (hist_f, hist_g) contributions, (Q, L+3).

    One block of codes/points in, per-level frequent and good histogram
    contributions out — level computation, distance, good-level ceil and
    one-hot binning in a single composite.  Bin L+2 collects excluded
    rows and is sliced off by the caller.
    """
    L = n_levels
    lf = _fused_lf(codes_b, codes_q, mu, beta_q, row_ok, c, L, unroll)
    dist = per_query_dist(queries, q_weight, points_b, p)
    jg = jnp.ceil(
        jnp.maximum(log_c(jnp.maximum(dist, 1e-30), c)
                    - log_c(c * r_min, c)[:, None], 0.0)
    ).astype(jnp.int32)
    good = jnp.where(row_ok[None, :], jnp.maximum(lf, jg), jnp.int32(L + 2))
    levels = jnp.arange(L + 3, dtype=jnp.int32)
    hist_f = jnp.sum(
        (lf[:, :, None] == levels[None, None, :]).astype(jnp.int32), axis=1
    )
    hist_g = jnp.sum(
        (good[:, :, None] == levels[None, None, :]).astype(jnp.int32), axis=1
    )
    return hist_f, hist_g


@functools.partial(
    jax.jit, static_argnames=("c", "n_levels", "p", "unroll")
)
def fused_query_scores_ref(codes_b, points_b, codes_q, queries, q_weight, mu,
                           beta_q, stop, row_ok, c: int, n_levels: int,
                           p: float, unroll: bool = False):
    """Pass-2 fused block step: (Q, B) stop-masked weighted distances.

    Rows whose first-frequent level exceeds the query's stop level — and
    every excluded row — score +inf, ready for the engine's running
    top-k.  ``stop <= n_levels`` always, so the L+2 exclusion sentinel
    can never pass the mask.
    """
    lf = _fused_lf(codes_b, codes_q, mu, beta_q, row_ok, c, n_levels, unroll)
    dist = per_query_dist(queries, q_weight, points_b, p)
    return jnp.where(lf <= stop[:, None], dist, jnp.inf)
