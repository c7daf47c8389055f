"""Pallas TPU kernel: fused WLSH query block step (levels + distances).

One launch per scan block runs three stages (first-frequent level,
weighted distance, histogram / mask) with the (q, block) intermediates
held in VMEM — the level matrix and the distance matrix never round-trip
through HBM between stages.  Two modes, one per engine pass:

  pass 1 (hist):   codes + points tile -> first-frequent level, weighted
                   l_p distance, good-level ceil, and per-level one-hot
                   histogram contributions (frequent + good), with the
                   streaming ``n_valid`` dead-row mask folded in.
  pass 2 (scores): codes + points tile -> first-frequent level + weighted
                   l_p distances masked by the query's stop level, ready
                   for the engine's running top-k.

Both passes find the first-frequent level the same way: for each level j
the query row's codes are floor-divided by c once more, turned into the
bucket bounds [q_j c^j, (q_j + 1) c^j - 1] (clipped to int32, empty past
the member's beta_q), and the point tile's codes are counted inside them
with two compares.  That is exactly the oracle's floor(a / c^j) ==
floor(b / c^j) test, with every integer division on the (1, beta) query
row and none on the (BN, beta) point tile, where the TPU would expand it
into long multiply/shift sequences per vreg.

Grid: (n_live, block/BN).  ``n_live`` is the launch's count of live
query rows, a run-time int32 scalar that becomes a dynamic grid bound:
the serving batch pads to Q rows by repeating live ones, and a step for
a padding row would compute an answer nobody reads, so none is run.
Rows at or past ``n_live`` are defined by the wrapper instead (zero
histograms, +inf scores).  Query code row (1, beta) and point codes
(BN, beta) stay whole in the lane axis, as do the (1, d)/(BN, d) vector
tiles.
Per-query operands and outputs are passed as (Q, 1, X) with the query
axis squeezed out of the block, so every block's last two dims equal the
array's and Mosaic accepts any Q (a (1, X) block of a (Q, X) array is
refused once Q > 1).  The p = 2 distance runs the norms+matmul
expansion on the MXU inside the kernel (two (1, d) x (d, BN)
contractions), p != 2 is a VPU reduction.
VMEM per grid step at BN=256, beta<=1024, d<=1024: ~1 MB codes + ~1 MB
vectors + ~128 KB histogram scratch.  Per-query scalars (mu, beta_q,
r_min / stop) ride in SMEM; the block's global row offset and the
streaming row watermark are (1, 1) SMEM scalars shared by every cell.

Histogram bins use a 128-lane-padded axis (``_nbins``); excluded rows
(block padding or rows at/after ``n_valid``) land in bin n_levels + 2,
which the ops wrapper slices off.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_query_hist_pallas", "fused_query_scores_pallas", "nbins"]


def nbins(n_levels: int) -> int:
    """Lane-padded histogram width covering bins 0..n_levels+2."""
    return 128 * math.ceil((n_levels + 3) / 128)


_I32_MIN, _I32_MAX = -(2**31), 2**31 - 1


def _floor_div(x, c: int):
    # lax integer div truncates toward zero; emulate floor for negatives.
    q = jax.lax.div(x, jnp.int32(c))
    r = jax.lax.rem(x, jnp.int32(c))
    neg = (r != 0) & ((r < 0) != (c < 0))
    return q - jnp.where(neg, 1, 0).astype(jnp.int32)


def _bucket_bounds(q, cj: int):
    """int32 codes a with floor(a / cj) == q, as inclusive [lo, hi].

    ``q`` is the query's level-j bucket floor(b / cj) and ``cj`` = c**j a
    Python int.  The bucket [q*cj, (q+1)*cj - 1] is clipped to the int32
    range, where every code lies, so neither end overflows.
    """
    if cj > _I32_MAX:  # q is -1 or 0: every negative or every other code
        neg = q < 0
        return (jnp.where(neg, _I32_MIN, 0).astype(jnp.int32),
                jnp.where(neg, -1, _I32_MAX).astype(jnp.int32))
    q_lo = -((2**31) // cj)  # least q whose bucket starts in range
    q_hi = (2**31 - cj) // cj  # greatest q whose bucket ends in range
    lo = jnp.where(q >= q_lo, jnp.maximum(q, q_lo) * cj, _I32_MIN)
    hi = jnp.where(q <= q_hi, jnp.minimum(q, q_hi) * cj + (cj - 1), _I32_MAX)
    return lo, hi


def _lf_and_dist(cq_ref, cp_ref, qpt_ref, ppt_ref, w_ref, mu_ref, bq_ref,
                 *, c: int, n_levels: int, p: float):
    """(1, BN) first-frequent level + (1, BN) weighted l_p distance.

    A point collides with the query at level j when floor(a / c**j) ==
    floor(b / c**j), i.e. when a lies in the query's level-j bucket.  The
    bucket bounds come from the (1, beta) query row, so the (BN, beta)
    point tile is only compared, never divided.  Lanes at or past the
    member's ``beta_q`` get the empty bucket [1, 0].
    """
    a = cp_ref[...].astype(jnp.int32)  # (BN, beta)
    q = cq_ref[...].astype(jnp.int32)  # (1, beta)
    mu = mu_ref[0, 0]
    lane_ok = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1) < bq_ref[0, 0]
    never = jnp.int32(n_levels + 1)
    lf = jnp.full((1, a.shape[0]), never, jnp.int32)
    for j in range(n_levels + 1):
        lo, hi = _bucket_bounds(q, c**j)
        lo = jnp.where(lane_ok, lo, 1)
        hi = jnp.where(lane_ok, hi, 0)
        cnt = jnp.sum(((a >= lo) & (a <= hi)).astype(jnp.int32), axis=1)
        lf = jnp.where((cnt[None, :] >= mu) & (lf == never), jnp.int32(j), lf)
        q = _floor_div(q, c)

    x = ppt_ref[...]  # (BN, d)
    qv = qpt_ref[...]  # (1, d)
    w = w_ref[...]  # (1, d)
    if abs(p - 2.0) < 1e-9:
        w2 = w * w
        qw2 = jnp.sum(w2 * qv * qv)
        # full f32 contractions: the expansion cancels large terms, so a
        # bf16 pass would move distances across good-level boundaries
        cross = jax.lax.dot_general(
            w2 * qv, x, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (1, BN)
        onorm = jax.lax.dot_general(
            w2, x * x, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (1, BN)
        d2 = qw2 - 2.0 * cross + onorm
        dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    else:
        diff = jnp.abs((qv - x) * w)  # (BN, d)
        if abs(p - 1.0) < 1e-9:
            dist = jnp.sum(diff, axis=1)[None, :]
        else:
            dist = (jnp.sum(diff**p, axis=1) ** (1.0 / p))[None, :]
    return lf, dist


def _row_ok(boff_ref, nvalid_ref, bn: int, n_rows: int):
    """(1, BN) live-row mask: inside the unpadded block AND below n_valid."""
    ip = pl.program_id(1)
    row = ip * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    return (row < n_rows) & ((boff_ref[0, 0] + row) < nvalid_ref[0, 0])


def _hist_kernel(cq_ref, cp_ref, qpt_ref, ppt_ref, w_ref, mu_ref, bq_ref,
                 rmin_ref, boff_ref, nvalid_ref, of_ref, og_ref,
                 accf_ref, accg_ref, *, c: int, n_levels: int, p: float,
                 n_rows: int, n_tiles: int, n_bins: int):
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        accf_ref[...] = jnp.zeros_like(accf_ref)
        accg_ref[...] = jnp.zeros_like(accg_ref)

    lf, dist = _lf_and_dist(cq_ref, cp_ref, qpt_ref, ppt_ref, w_ref,
                            mu_ref, bq_ref, c=c, n_levels=n_levels, p=p)
    bn = lf.shape[1]
    ok = _row_ok(boff_ref, nvalid_ref, bn, n_rows)
    excl = jnp.int32(n_levels + 2)
    base = jnp.log(c * rmin_ref[0, 0]) / math.log(c)
    jg = jnp.ceil(
        jnp.maximum(jnp.log(jnp.maximum(dist, 1e-30)) / math.log(c) - base,
                    0.0)
    ).astype(jnp.int32)
    lf_x = jnp.where(ok, lf, excl)
    good = jnp.where(ok, jnp.maximum(lf, jg), excl)
    bins = jax.lax.broadcasted_iota(jnp.int32, (n_bins, bn), 0)
    accf_ref[...] += jnp.sum((bins == lf_x).astype(jnp.int32), axis=1)[None, :]
    accg_ref[...] += jnp.sum((bins == good).astype(jnp.int32), axis=1)[None, :]

    @pl.when(ip == n_tiles - 1)
    def _epilogue():
        of_ref[...] = accf_ref[...]
        og_ref[...] = accg_ref[...]


def _scores_kernel(cq_ref, cp_ref, qpt_ref, ppt_ref, w_ref, mu_ref, bq_ref,
                   stop_ref, boff_ref, nvalid_ref, o_ref, *, c: int,
                   n_levels: int, p: float, n_rows: int):
    lf, dist = _lf_and_dist(cq_ref, cp_ref, qpt_ref, ppt_ref, w_ref,
                            mu_ref, bq_ref, c=c, n_levels=n_levels, p=p)
    ok = _row_ok(boff_ref, nvalid_ref, lf.shape[1], n_rows)
    keep = ok & (lf <= stop_ref[0, 0])
    o_ref[...] = jnp.where(keep, dist, jnp.inf)


def _specs(beta: int, d: int, bn: int):
    """Common in_specs prefix: codes/vectors/weight tiles + SMEM scalars."""
    smem_q = pl.BlockSpec(
        (None, 1, 1), lambda iq, ip: (iq, 0, 0), memory_space=pltpu.SMEM
    )
    smem_g = pl.BlockSpec(
        (1, 1), lambda iq, ip: (0, 0), memory_space=pltpu.SMEM
    )
    tiles = [
        pl.BlockSpec((None, 1, beta), lambda iq, ip: (iq, 0, 0)),
        pl.BlockSpec((bn, beta), lambda iq, ip: (ip, 0)),
        pl.BlockSpec((None, 1, d), lambda iq, ip: (iq, 0, 0)),
        pl.BlockSpec((bn, d), lambda iq, ip: (ip, 0)),
        pl.BlockSpec((None, 1, d), lambda iq, ip: (iq, 0, 0)),  # weight
    ]
    return tiles, smem_q, smem_g


def _as_col(v, dtype):
    return jnp.asarray(v, dtype).reshape(-1, 1)


def _per_query_scalar(v, dtype):
    """(Q,) -> (Q, 1, 1): one SMEM scalar per grid step (as ``_per_query_rows``)."""
    return jnp.asarray(v, dtype).reshape(-1, 1, 1)


def _per_query_rows(x, dtype):
    """(Q, X) -> (Q, 1, X): one query row per grid step.

    Mosaic requires a block's last two dims to be (8, 128)-aligned or to
    equal the array's; a (1, X) block of a (Q, X) array is neither once
    Q > 1.  The leading query axis is squeezed out of the block instead,
    so each kernel invocation still sees a (1, X) row.
    """
    return x.astype(dtype).reshape(x.shape[0], 1, x.shape[1])


def _live_rows(q: int, n_live):
    """(grid's query bound, (Q, 1) mask of the rows the kernel writes).

    ``n_live`` None means all Q rows.  The bound is clipped to [0, Q] so
    no grid step indexes past the batch.
    """
    n_live = jnp.clip(jnp.asarray(q if n_live is None else n_live,
                                  jnp.int32), 0, q)
    return n_live, jnp.arange(q, dtype=jnp.int32)[:, None] < n_live


@functools.partial(
    jax.jit,
    static_argnames=("c", "n_levels", "p", "bn", "n_rows", "interpret"),
)
def fused_query_hist_pallas(
    codes_p,  # (B_pad, beta) int32
    points,  # (B_pad, d) f32
    codes_q,  # (Q, beta) int32
    queries,  # (Q, d) f32
    q_weight,  # (Q, d) f32
    mu,  # (Q,) int32
    beta_q,  # (Q,) int32
    r_min,  # (Q,) f32
    boff,  # () int32 global row offset of this block
    n_valid,  # () int32 streaming live-row watermark
    c: int,
    n_levels: int,
    p: float,
    n_rows: int,  # live rows in the block before padding
    bn: int = 256,
    interpret: bool = False,
    n_live=None,  # () int32 live query rows; None = all Q
):
    """Pass-1 fused block step -> (hist_f, hist_g), each (Q, nbins).

    Rows at or past ``n_live`` run no grid step and read all zero.
    """
    b_pad, beta = codes_p.shape
    q, d = queries.shape
    bn = min(bn, b_pad)
    assert b_pad % bn == 0, "caller (ops.py) must pad rows to block multiples"
    n_tiles = b_pad // bn
    n_bins = nbins(n_levels)
    kernel = functools.partial(
        _hist_kernel, c=int(c), n_levels=int(n_levels), p=float(p),
        n_rows=int(n_rows), n_tiles=n_tiles, n_bins=n_bins,
    )
    tiles, smem_q, smem_g = _specs(beta, d, bn)
    out_spec = pl.BlockSpec((None, 1, n_bins), lambda iq, ip: (iq, 0, 0))
    out_shape = jax.ShapeDtypeStruct((q, 1, n_bins), jnp.int32)
    grid_q, live = _live_rows(q, n_live)
    hf, hg = pl.pallas_call(
        kernel,
        grid=(grid_q, n_tiles),
        in_specs=tiles + [smem_q, smem_q, smem_q, smem_g, smem_g],
        out_specs=(out_spec, out_spec),
        out_shape=(out_shape, out_shape),
        scratch_shapes=[
            pltpu.VMEM((1, n_bins), jnp.int32),
            pltpu.VMEM((1, n_bins), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        name="fused_query_hist",
    )(
        _per_query_rows(codes_q, jnp.int32),
        codes_p.astype(jnp.int32),
        _per_query_rows(queries, jnp.float32),
        points.astype(jnp.float32),
        _per_query_rows(q_weight, jnp.float32),
        _per_query_scalar(mu, jnp.int32),
        _per_query_scalar(beta_q, jnp.int32),
        _per_query_scalar(r_min, jnp.float32),
        _as_col(boff, jnp.int32),
        _as_col(n_valid, jnp.int32),
    )
    return (jnp.where(live, hf.reshape(q, n_bins), 0),
            jnp.where(live, hg.reshape(q, n_bins), 0))


@functools.partial(
    jax.jit,
    static_argnames=("c", "n_levels", "p", "bn", "n_rows", "interpret"),
)
def fused_query_scores_pallas(
    codes_p,  # (B_pad, beta) int32
    points,  # (B_pad, d) f32
    codes_q,  # (Q, beta) int32
    queries,  # (Q, d) f32
    q_weight,  # (Q, d) f32
    mu,  # (Q,) int32
    beta_q,  # (Q,) int32
    stop,  # (Q,) int32 per-query stop level
    boff,  # () int32
    n_valid,  # () int32
    c: int,
    n_levels: int,
    p: float,
    n_rows: int,
    bn: int = 256,
    interpret: bool = False,
    n_live=None,  # () int32 live query rows; None = all Q
):
    """Pass-2 fused block step -> (Q, B_pad) stop-masked distances.

    Rows at or past ``n_live`` run no grid step and read +inf.
    """
    b_pad, beta = codes_p.shape
    q, d = queries.shape
    bn = min(bn, b_pad)
    assert b_pad % bn == 0, "caller (ops.py) must pad rows to block multiples"
    kernel = functools.partial(
        _scores_kernel, c=int(c), n_levels=int(n_levels), p=float(p),
        n_rows=int(n_rows),
    )
    tiles, smem_q, smem_g = _specs(beta, d, bn)
    grid_q, live = _live_rows(q, n_live)
    out = pl.pallas_call(
        kernel,
        grid=(grid_q, b_pad // bn),
        in_specs=tiles + [smem_q, smem_q, smem_q, smem_g, smem_g],
        out_specs=pl.BlockSpec((None, 1, bn), lambda iq, ip: (iq, 0, ip)),
        out_shape=jax.ShapeDtypeStruct((q, 1, b_pad), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        name="fused_query_scores",
    )(
        _per_query_rows(codes_q, jnp.int32),
        codes_p.astype(jnp.int32),
        _per_query_rows(queries, jnp.float32),
        points.astype(jnp.float32),
        _per_query_rows(q_weight, jnp.float32),
        _per_query_scalar(mu, jnp.int32),
        _per_query_scalar(beta_q, jnp.int32),
        _per_query_scalar(stop, jnp.int32),
        _as_col(boff, jnp.int32),
        _as_col(n_valid, jnp.int32),
    )
    return jnp.where(live, out.reshape(q, b_pad), jnp.inf)
