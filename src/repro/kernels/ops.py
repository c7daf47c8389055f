"""The jit-traced dispatch of one fused query block step.

``fused_query_block`` is the engine's per-scan-block launch (pass-1
histograms or pass-2 stop-masked scores).  It runs the Pallas kernels in
fused_query.py (compiled on TPU, or in interpret mode) or their fused XLA
composite in ref.py; a caller that names neither gets the backend's
``platform.scan_mode()``.  Both routes take the block as the group state
stores it, point axis minor: codes (beta, B) and vectors (d, B).  The
Pallas route launches on it as it is, with nothing padded or transposed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import platform, ref
from .fused_query import (
    ROW_TILE,
    fused_query_hist_pallas,
    fused_query_scores_pallas,
)

__all__ = ["fused_query_block", "gather_columns"]


def _kernel_flags(use_pallas, interpret):
    """(use_pallas, interpret); ``use_pallas=None`` reads the scan mode."""
    if use_pallas is None:
        mode = platform.scan_mode()
        return mode != "xla", mode == "interpret"
    return use_pallas, interpret


def fused_query_block(
    codes_p,  # (beta, B) int32 — one scan block of point codes
    points,  # (d, B) — the matching vector block (any float dtype)
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
    use_pallas: bool | None = None,  # None = the backend's scan mode
    interpret: bool = False,  # with use_pallas=True: run the body interpreted
    bn: int = ROW_TILE,
    unroll: bool = False,
):
    """One fused query block step — the engine's per-scan-block launch.

    Pass 1 (``stop=None``) returns ``(hist_f, hist_g)`` per-level
    frequent/good histogram contributions, each ``(Q, n_levels + 2)``
    int32 (bins 0..n_levels+1; excluded rows, those at or beyond
    ``n_valid``, are dropped entirely).  Pass 2 (``stop`` given) returns
    ``(Q, B)`` f32 distances with rows past the query's stop level (and
    excluded rows) masked to +inf, ready for a running top-k.

    The XLA route is the fused composite in ref.py, built from the ref.py
    stage oracles (``freq_level_ref``, ``per_query_dist``).  The Pallas
    route runs the whole step as one kernel launch (see fused_query.py);
    there only, rows at or past ``n_live`` run no grid step and read zero
    histograms and +inf scores.  The XLA route ignores ``n_live``.
    """
    use_pallas, interpret = _kernel_flags(use_pallas, interpret)
    beta, b = codes_p.shape
    q = codes_q.shape[0]
    mu = jnp.broadcast_to(jnp.asarray(mu, jnp.int32), (q,))
    r_min = jnp.broadcast_to(jnp.asarray(r_min, jnp.float32), (q,))
    if beta_q is None:
        beta_q = jnp.full((q,), beta, jnp.int32)
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

    kw = dict(c=c, n_levels=n_levels, p=p, bn=bn, interpret=interpret,
              n_live=n_live)
    if stop is None:
        hf, hg = fused_query_hist_pallas(
            codes_p, pts, codes_q, qs, w, mu, beta_q, r_min, boff, n_valid,
            **kw)
        return hf[:, : n_levels + 2], hg[:, : n_levels + 2]
    return fused_query_scores_pallas(
        codes_p, pts, codes_q, qs, w, mu, beta_q, stop, boff, n_valid, **kw)


def gather_columns(points, rows, *, n_live=None):
    """``points[:, rows]`` as (q, k, d): the vectors of a (d, n) block's
    columns ``rows`` (q, k), for the exact re-rank of the winners.

    A loop reads one (d, 1) column per candidate of the live rows: a
    gather of single columns makes the chip's compiler transpose the whole
    (d, n) state first.  Rows at or past ``n_live`` are padding, scored
    +inf, and read zeros here.
    """
    q, k = rows.shape
    flat = rows.reshape(-1)
    n_live = q if n_live is None else jnp.clip(n_live, 0, q)

    def body(i, out):
        col = jax.lax.dynamic_slice_in_dim(points, flat[i], 1, 1)
        return jax.lax.dynamic_update_slice_in_dim(out, col, i, 1)

    out = jax.lax.fori_loop(0, n_live * k, body,
                            jnp.zeros((points.shape[0], q * k), points.dtype))
    return out.T.reshape(q, k, -1)
