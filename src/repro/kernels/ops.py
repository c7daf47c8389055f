"""Public jit'd wrappers around the Pallas kernels.

Each op pads inputs to kernel block multiples, dispatches to the Pallas
kernel (interpret=True off-TPU so the same kernel body runs everywhere),
and masks the padding out of the result.  ``use_pallas=False`` routes to
the pure-jnp oracle in ref.py; ``use_pallas=None`` resolves per backend
through ``kernels.platform`` (compiled Pallas where supported, reference
elsewhere) and ``use_pallas="interpret"`` forces the Pallas body in
interpret mode — the same kernel code, executable on every backend.

``fused_query_block`` is the engine's fused per-block query step (pass-1
histograms or pass-2 stop-masked scores in one launch); its reference
route is the fused XLA composite in ref.py, which shares the unfused
engine's distance helpers and is therefore bit-exact with it.

p == 2 distance scoring in the *unfused* ops always uses the norms+matmul
expansion (MXU beats any elementwise kernel for the quadratic case); the
fused kernel runs the same expansion on the MXU inside the kernel body.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import platform, ref
from .freq_level import freq_level_pallas
from .fused_query import fused_query_hist_pallas, fused_query_scores_pallas
from .hash_encode import hash_encode_pallas
from .weighted_lp import weighted_lp_pallas

__all__ = [
    "hash_encode",
    "freq_level",
    "weighted_lp_dist",
    "fused_query_block",
    "on_tpu",
]

# Back-compat alias; the cached query lives in kernels.platform now.
on_tpu = platform.on_tpu


def _resolve_flags(use_pallas, interpret):
    """Normalize (use_pallas, interpret) through the cached backend."""
    if use_pallas == "interpret":
        return True, True
    if use_pallas is None:
        use_pallas = platform.default_use_pallas()
    if interpret is None:
        interpret = not platform.on_tpu()
    return use_pallas, interpret


def _pad_to(x, mult: int, axis: int, value=0):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value)


def hash_encode(
    points,
    weight,
    proj,
    b_int,
    b_frac,
    width: float,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    bn: int = 256,
    bb: int = 128,
    bd: int = 256,
):
    """(n, beta) int32 level-1 bucket codes."""
    use_pallas, interpret = _resolve_flags(use_pallas, interpret)
    if not use_pallas:
        return ref.hash_encode_ref(points, proj, b_int, b_frac, weight, width)
    n, d = points.shape
    beta = proj.shape[1]
    pts = _pad_to(_pad_to(points, bn, 0), bd, 1)
    w = _pad_to(weight, bd, 0)
    a = _pad_to(_pad_to(proj, bd, 0), bb, 1)
    bi = _pad_to(b_int, bb, 0)
    bf = _pad_to(b_frac, bb, 0)
    out = hash_encode_pallas(
        pts, w, a, bi, bf, width, bn=bn, bb=bb, bd=bd, interpret=interpret
    )
    return out[:n, :beta]


def freq_level(
    codes_p,
    codes_q,
    mu,
    c: int,
    n_levels: int,
    beta_q=None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    bn: int = 256,
    unroll: bool = False,
):
    """(Q, n) int32 first-frequent-level matrix (n_levels+1 = never)."""
    use_pallas, interpret = _resolve_flags(use_pallas, interpret)
    q = codes_q.shape[0]
    mu = jnp.broadcast_to(jnp.asarray(mu, jnp.int32), (q,))
    if beta_q is None:
        beta_q = jnp.full((q,), codes_p.shape[1], jnp.int32)
    beta_q = jnp.broadcast_to(jnp.asarray(beta_q, jnp.int32), (q,))
    if not use_pallas:
        return ref.freq_level_ref(codes_p, codes_q, mu, c, n_levels, beta_q,
                                  unroll=unroll)
    n = codes_p.shape[0]
    cp = _pad_to(codes_p, bn, 0, value=jnp.iinfo(jnp.int32).max // 2)
    out = freq_level_pallas(
        cp, codes_q, mu, beta_q, c=c, n_levels=n_levels, bn=bn,
        interpret=interpret,
    )
    return out[:, :n]


def weighted_lp_dist(
    queries,
    points,
    weight,
    p: float,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    bn: int = 256,
    bd: int = 256,
):
    """(Q, n) f32 weighted l_p distances."""
    use_pallas, interpret = _resolve_flags(use_pallas, interpret)
    if abs(p - 2.0) < 1e-9 or not use_pallas:
        return ref.weighted_lp_ref(queries, points, weight, p)
    qn, d = queries.shape
    n = points.shape[0]
    q = _pad_to(queries, bd, 1)
    x = _pad_to(_pad_to(points, bn, 0), bd, 1)
    w = _pad_to(weight, bd, 0)
    out = weighted_lp_pallas(q, x, w, p=p, bn=bn, bd=bd, interpret=interpret)
    return out[:, :n]


def fused_query_block(
    codes_p,  # (B, beta) int32 — one scan block of point codes
    points,  # (B, d) — the matching vector block (any float dtype)
    codes_q,  # (Q, beta) int32 query bucket codes
    queries,  # (Q, d) query vectors
    q_weight,  # (Q, d) per-query weight vectors
    mu,  # (Q,) or scalar int32 collision thresholds
    r_min,  # (Q,) or scalar f32 radius bases (pass-1 good-level ceil)
    beta_q,  # (Q,) or scalar int32 per-member table counts; None = all
    *,
    boff,  # () int32 global row offset of this block
    n_valid,  # () int32 streaming live-row watermark (rows >= it are dead)
    c: int,
    n_levels: int,
    p: float,
    stop=None,  # None = pass-1 (histograms); (Q,) int32 = pass-2 (scores)
    n_live=None,  # () int32 live query rows (rows >= it are padding); None = Q
    use_pallas: bool | str | None = None,
    interpret: bool | None = None,
    bn: int = 256,
    unroll: bool = False,
):
    """One fused query block step — the engine's per-scan-block launch.

    Pass 1 (``stop=None``) returns ``(hist_f, hist_g)`` per-level
    frequent/good histogram contributions, each ``(Q, n_levels + 2)``
    int32 (bins 0..n_levels+1; excluded rows — block padding and rows at
    or beyond ``n_valid`` — are dropped entirely).  Pass 2 (``stop``
    given) returns ``(Q, B)`` f32 distances with rows past the query's
    stop level (and excluded rows) masked to +inf, ready for a running
    top-k.

    The reference route is the fused XLA composite in ref.py, which
    reuses the unfused engine's distance helpers on identical shapes and
    is therefore bit-exact with the unfused scan.  The Pallas route runs
    the whole step as one kernel launch (see fused_query.py); there only,
    rows at or past ``n_live`` run no grid step and read zero histograms
    and +inf scores.  The reference route ignores ``n_live``.
    """
    use_pallas, interpret = _resolve_flags(use_pallas, interpret)
    b, _ = codes_p.shape
    q = codes_q.shape[0]
    mu = jnp.broadcast_to(jnp.asarray(mu, jnp.int32), (q,))
    r_min = jnp.broadcast_to(jnp.asarray(r_min, jnp.float32), (q,))
    if beta_q is None:
        beta_q = jnp.full((q,), codes_p.shape[1], jnp.int32)
    beta_q = jnp.broadcast_to(jnp.asarray(beta_q, jnp.int32), (q,))
    if stop is not None:
        stop = jnp.broadcast_to(jnp.asarray(stop, jnp.int32), (q,))
    boff = jnp.asarray(boff, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    pts = points.astype(jnp.float32)
    qs = queries.astype(jnp.float32)
    w = q_weight.astype(jnp.float32)

    if not use_pallas:
        row_ok = (boff + jnp.arange(b, dtype=jnp.int32)) < n_valid
        if stop is None:
            hf, hg = ref.fused_query_hist_ref(
                codes_p, pts, codes_q, qs, w, mu, beta_q, r_min, row_ok,
                c=c, n_levels=n_levels, p=p, unroll=unroll,
            )
            return hf[:, : n_levels + 2], hg[:, : n_levels + 2]
        return ref.fused_query_scores_ref(
            codes_p, pts, codes_q, qs, w, mu, beta_q, stop, row_ok,
            c=c, n_levels=n_levels, p=p, unroll=unroll,
        )

    cp = _pad_to(codes_p, bn, 0, value=jnp.iinfo(jnp.int32).max // 2)
    xp = _pad_to(_pad_to(pts, bn, 0), 128, 1)
    qsp = _pad_to(qs, 128, 1)
    wp = _pad_to(w, 128, 1)
    if stop is None:
        hf, hg = fused_query_hist_pallas(
            cp, xp, codes_q, qsp, wp, mu, beta_q, r_min, boff, n_valid,
            c=c, n_levels=n_levels, p=p, n_rows=b, bn=bn,
            interpret=interpret, n_live=n_live,
        )
        return hf[:, : n_levels + 2], hg[:, : n_levels + 2]
    out = fused_query_scores_pallas(
        cp, xp, codes_q, qsp, wp, mu, beta_q, stop, boff, n_valid,
        c=c, n_levels=n_levels, p=p, n_rows=b, bn=bn, interpret=interpret,
        n_live=n_live,
    )
    return out[:, :b]
