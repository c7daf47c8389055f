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

Tiles are point-axis-minor, as the group state lies in HBM: a (beta, BN)
code tile and a (d, BN) vector tile, points along lanes.  The kernels
read the state in place; nothing is transposed or padded per launch.

Both passes find the first-frequent level the same way.  For each level
j the wrapper turns the query's codes into the bucket bounds
[q_j c^j, (q_j + 1) c^j - 1] (``_level_bounds``: floor-divided by c once
more per level, clipped to int32, empty past the member's beta_q), once
per launch and on the (Q, beta) query codes only.  A query row's first
tile copies its (beta, 2 (L + 1)) bounds into a VMEM scratch with each
bound repeated along the 128 lanes; every tile then counts its codes
inside each level's bounds with two compares and a sublane sum that
lands as the (1, BN) level row.  That is exactly the oracle's
floor(a / c^j) == floor(b / c^j) test, with no integer division and no
lane broadcast per tile.

Grid: (n_live, cdiv(block, BN)).  ``n_live`` is the launch's count of
live query rows, a run-time int32 scalar that becomes a dynamic grid
bound: the serving batch pads to Q rows by repeating live ones, and a
step for a padding row would compute an answer nobody reads, so none is
run.  Rows at or past ``n_live`` are defined by the wrapper instead
(zero histograms, +inf scores).  A block whose rows are not whole tiles
(standalone callers only: served states hold whole tiles) gets a
partial last tile whose rows past the block are masked.
Per-query operands and outputs are passed as (Q, ...) arrays with the
query axis squeezed out of the block, so every block's last two dims
equal the array's and Mosaic accepts any Q (a (1, X) block of a (Q, X)
array is refused once Q > 1).  The p = 2 distance is the norms+matmul
expansion on the MXU inside the kernel, two (1, d) x (d, BN)
contractions of (1, d) query rows; p != 2 reduces |(q - x) w|^p over
sublanes against (d, 1) query columns.
VMEM per grid step at BN=256, beta<=1024, d<=1024: ~1 MB codes + ~1 MB
vectors, double-buffered, + the (beta, 2 (L + 1)) bounds + the
replicated bounds, (2 (L + 1), beta, 128) int32 (8-9 MB at the
benchmark's widths) + ~128 KB histogram scratch.
Per-query scalars (mu, r_min / stop) ride in SMEM; the block's global
row offset and the streaming row watermark are (1, 1) SMEM scalars
shared by every cell.

Histogram bins use a 128-lane-padded axis (``_nbins``); excluded rows
(a partial tile's rows past the block, or rows at/after ``n_valid``)
land in bin n_levels + 2, which the ops wrapper slices off.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "ROW_TILE",
    "fused_query_hist_pallas",
    "fused_query_scores_pallas",
    "nbins",
]


def nbins(n_levels: int) -> int:
    """Lane-padded histogram width covering bins 0..n_levels+2."""
    return 128 * math.ceil((n_levels + 3) / 128)


# Points per kernel tile (the lane extent of a (beta, BN) code tile).  A
# served state's rows per shard are a multiple of it, so every launch
# runs whole tiles (``serving.batching.Batcher.row_capacity``).
ROW_TILE = 256

_I32_MIN, _I32_MAX = -(2**31), 2**31 - 1
# Scoped VMEM of one launch: the replicated bounds plus room for the
# double-buffered tiles, under the v5e's 128 MiB of VMEM.
_VMEM_BASE, _VMEM_CAP = 32 * 2**20, 100 * 2**20


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


def _level_bounds(codes_q, beta_q, *, c: int, n_levels: int):
    """(Q, beta, 2 (L + 1)) int32 level-bucket bounds, one table per row.

    Lane j holds lo and lane L + 1 + j holds hi of level j's bucket
    [q_j c^j, (q_j + 1) c^j - 1], for j = 0..L.  Tables at or past the
    member's ``beta_q`` get the empty bucket [1, 0].  Computed once per
    launch on the (Q, beta) query codes, outside the kernels.
    """
    q = jnp.asarray(codes_q, jnp.int32)
    lane_ok = (jnp.arange(q.shape[1], dtype=jnp.int32)[None, :]
               < jnp.asarray(beta_q, jnp.int32)[:, None])
    los, his = [], []
    for j in range(n_levels + 1):
        lo, hi = _bucket_bounds(q, c**j)
        los.append(jnp.where(lane_ok, lo, 1))
        his.append(jnp.where(lane_ok, hi, 0))
        q = _floor_div(q, c)
    return jnp.stack(los + his, axis=-1)


def _lane_width(bn: int) -> int:
    """Lanes of one replicated bound tile: a vreg's 128, or a narrow
    block's own width."""
    return 128 if bn % 128 == 0 else bn


def _replicate_bounds(bnd_ref, rep_ref):
    """Copy the query's (beta, 2 (L + 1)) bounds into ``rep_ref`` as
    (beta, lanes) tiles, each bound repeated along every lane.

    Run once per query row (the first tile of its row): a lane broadcast
    costs the cross-lane unit several cycles a vreg, where the level loop
    then reads each replicated tile with plain loads.
    """
    bnd = bnd_ref[...]
    for k in range(rep_ref.shape[0]):
        rep_ref[k] = jnp.broadcast_to(bnd[:, k:k + 1], rep_ref.shape[1:])


def _lf_and_dist(rep_ref, cp_ref, qv_ref, ppt_ref, w_ref, mu_ref,
                 *, n_levels: int, p: float):
    """(1, BN) first-frequent level + (1, BN) weighted l_p distance.

    A point collides with the query at level j when its code lies in the
    query's level-j bucket: the (beta, BN) tile is compared against the
    level's lane-replicated bounds and the hits summed over sublanes.
    """
    a = cp_ref[...].astype(jnp.int32)  # (beta, BN)
    reps = a.shape[1] // rep_ref.shape[2]
    mu = mu_ref[0, 0]
    never = jnp.int32(n_levels + 1)
    lf = jnp.full((1, a.shape[1]), never, jnp.int32)
    for j in range(n_levels + 1):
        lo = jnp.concatenate([rep_ref[j]] * reps, axis=1)
        hi = jnp.concatenate([rep_ref[n_levels + 1 + j]] * reps, axis=1)
        cnt = jnp.sum(((a >= lo) & (a <= hi)).astype(jnp.int32), axis=0,
                      keepdims=True)  # (1, BN)
        lf = jnp.where((cnt >= mu) & (lf == never), jnp.int32(j), lf)

    x = ppt_ref[...]  # (d, BN)
    if abs(p - 2.0) < 1e-9:
        qv = qv_ref[...]  # (1, d)
        w2 = w_ref[...] ** 2  # (1, d)
        qw2 = jnp.sum(w2 * qv * qv)
        # full f32 contractions: the expansion cancels large terms, so a
        # bf16 pass would move distances across good-level boundaries
        cross = jax.lax.dot_general(
            w2 * qv, x, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (1, BN)
        onorm = jax.lax.dot_general(
            w2, x * x, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (1, BN)
        d2 = qw2 - 2.0 * cross + onorm
        dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    else:
        diff = jnp.abs((qv_ref[...] - x) * w_ref[...])  # (d, BN)
        if abs(p - 1.0) < 1e-9:
            dist = jnp.sum(diff, axis=0, keepdims=True)
        else:
            dist = jnp.sum(diff**p, axis=0, keepdims=True) ** (1.0 / p)
    return lf, dist


def _row_ok(boff_ref, nvalid_ref, bn: int, n_rows: int):
    """(1, BN) live-row mask: inside the block AND below n_valid."""
    row = pl.program_id(1) * bn + jax.lax.broadcasted_iota(
        jnp.int32, (1, bn), 1)
    ok = (boff_ref[0, 0] + row) < nvalid_ref[0, 0]
    if n_rows % bn:  # a partial last tile: its rows past the block
        ok = ok & (row < n_rows)
    return ok


def _hist_kernel(bnd_ref, cp_ref, qv_ref, ppt_ref, w_ref, mu_ref,
                 rmin_ref, boff_ref, nvalid_ref, of_ref, og_ref,
                 rep_ref, accf_ref, accg_ref, *, c: int, n_levels: int,
                 p: float, n_rows: int, n_tiles: int, n_bins: int):
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        _replicate_bounds(bnd_ref, rep_ref)
        accf_ref[...] = jnp.zeros_like(accf_ref)
        accg_ref[...] = jnp.zeros_like(accg_ref)

    lf, dist = _lf_and_dist(rep_ref, cp_ref, qv_ref, ppt_ref, w_ref,
                            mu_ref, n_levels=n_levels, p=p)
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


def _scores_kernel(bnd_ref, cp_ref, qv_ref, ppt_ref, w_ref, mu_ref,
                   stop_ref, boff_ref, nvalid_ref, o_ref, rep_ref, *,
                   n_levels: int, p: float, n_rows: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        _replicate_bounds(bnd_ref, rep_ref)

    lf, dist = _lf_and_dist(rep_ref, cp_ref, qv_ref, ppt_ref, w_ref,
                            mu_ref, n_levels=n_levels, p=p)
    ok = _row_ok(boff_ref, nvalid_ref, lf.shape[1], n_rows)
    keep = ok & (lf <= stop_ref[0, 0])
    o_ref[...] = jnp.where(keep, dist, jnp.inf)


def _per_query(*block):
    """The block of one query row's operand, the query axis squeezed."""
    return pl.BlockSpec((None,) + block,
                        lambda iq, ip: (iq,) + (0,) * len(block))


def _launch(codes_p, points, codes_q, queries, q_weight, mu, beta_q, *,
            c: int, n_levels: int, p: float, bn: int):
    """Shared set-up of both passes: tile size, tile count, the in_specs
    of the per-query and per-tile operands, those operands, the
    replicated-bounds scratch and the compiler parameters.

    The p = 2 contraction takes (1, d) query rows; p != 2 broadcasts
    (d, 1) query columns over the tile's lanes.  The tile axis runs in
    order ("arbitrary"): its first step fills the query row's scratch.
    """
    beta, b = codes_p.shape
    d = points.shape[0]
    bn = min(bn, b)
    vec = (1, d) if abs(p - 2.0) < 1e-9 else (d, 1)
    bounds = _level_bounds(codes_q, beta_q, c=c, n_levels=n_levels)
    specs = [
        _per_query(beta, bounds.shape[-1]),
        pl.BlockSpec((beta, bn), lambda iq, ip: (0, ip)),
        _per_query(*vec),
        pl.BlockSpec((d, bn), lambda iq, ip: (0, ip)),
        _per_query(*vec),  # weight
        _smem_q(),  # mu
    ]
    args = [
        bounds,
        codes_p.astype(jnp.int32),
        queries.astype(jnp.float32).reshape((-1,) + vec),
        points.astype(jnp.float32),
        q_weight.astype(jnp.float32).reshape((-1,) + vec),
        _per_query_scalar(mu, jnp.int32),
    ]
    rep_shape = (bounds.shape[-1], beta, _lane_width(bn))
    rep_bytes = 4 * math.prod(rep_shape)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=min(rep_bytes + _VMEM_BASE, _VMEM_CAP),
    )
    return (bn, pl.cdiv(b, bn), specs, args,
            pltpu.VMEM(rep_shape, jnp.int32), params)


def _smem_q():
    return pl.BlockSpec((None, 1, 1), lambda iq, ip: (iq, 0, 0),
                        memory_space=pltpu.SMEM)


def _smem_g():
    return pl.BlockSpec((1, 1), lambda iq, ip: (0, 0),
                        memory_space=pltpu.SMEM)


def _as_col(v, dtype):
    return jnp.asarray(v, dtype).reshape(-1, 1)


def _per_query_scalar(v, dtype):
    """(Q,) -> (Q, 1, 1): one SMEM scalar per grid step."""
    return jnp.asarray(v, dtype).reshape(-1, 1, 1)


def _live_rows(q: int, n_live):
    """(grid's query bound, (Q, 1) mask of the rows the kernel writes).

    ``n_live`` None means all Q rows.  The bound is clipped to [0, Q] so
    no grid step indexes past the batch.
    """
    n_live = jnp.clip(jnp.asarray(q if n_live is None else n_live,
                                  jnp.int32), 0, q)
    return n_live, jnp.arange(q, dtype=jnp.int32)[:, None] < n_live


@functools.partial(
    jax.jit, static_argnames=("c", "n_levels", "p", "bn", "interpret"),
)
def fused_query_hist_pallas(
    codes_p,  # (beta, B) int32, points along lanes
    points,  # (d, B) f32
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
    bn: int = ROW_TILE,
    interpret: bool = False,
    n_live=None,  # () int32 live query rows; None = all Q
):
    """Pass-1 fused block step -> (hist_f, hist_g), each (Q, nbins).

    Rows at or past ``n_live`` run no grid step and read all zero.
    """
    q = queries.shape[0]
    b = codes_p.shape[1]
    bn, n_tiles, specs, args, rep, params = _launch(
        codes_p, points, codes_q, queries, q_weight, mu, beta_q, c=c,
        n_levels=n_levels, p=p, bn=bn)
    n_bins = nbins(n_levels)
    kernel = functools.partial(
        _hist_kernel, c=int(c), n_levels=int(n_levels), p=float(p),
        n_rows=b, n_tiles=n_tiles, n_bins=n_bins,
    )
    out_spec = pl.BlockSpec((None, 1, n_bins), lambda iq, ip: (iq, 0, 0))
    out_shape = jax.ShapeDtypeStruct((q, 1, n_bins), jnp.int32)
    grid_q, live = _live_rows(q, n_live)
    hf, hg = pl.pallas_call(
        kernel,
        grid=(grid_q, n_tiles),
        in_specs=specs + [_smem_q(), _smem_g(), _smem_g()],
        out_specs=(out_spec, out_spec),
        out_shape=(out_shape, out_shape),
        scratch_shapes=[
            rep,
            pltpu.VMEM((1, n_bins), jnp.int32),
            pltpu.VMEM((1, n_bins), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=params,
        name="fused_query_hist",
    )(
        *args,
        _per_query_scalar(r_min, jnp.float32),
        _as_col(boff, jnp.int32),
        _as_col(n_valid, jnp.int32),
    )
    return (jnp.where(live, hf.reshape(q, n_bins), 0),
            jnp.where(live, hg.reshape(q, n_bins), 0))


@functools.partial(
    jax.jit, static_argnames=("c", "n_levels", "p", "bn", "interpret"),
)
def fused_query_scores_pallas(
    codes_p,  # (beta, B) int32, points along lanes
    points,  # (d, B) f32
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
    bn: int = ROW_TILE,
    interpret: bool = False,
    n_live=None,  # () int32 live query rows; None = all Q
):
    """Pass-2 fused block step -> (Q, B) stop-masked distances.

    Rows at or past ``n_live`` run no grid step and read +inf.
    """
    q = queries.shape[0]
    b = codes_p.shape[1]
    bn, n_tiles, specs, args, rep, params = _launch(
        codes_p, points, codes_q, queries, q_weight, mu, beta_q, c=c,
        n_levels=n_levels, p=p, bn=bn)
    kernel = functools.partial(
        _scores_kernel, n_levels=int(n_levels), p=float(p), n_rows=b,
    )
    grid_q, live = _live_rows(q, n_live)
    out = pl.pallas_call(
        kernel,
        grid=(grid_q, n_tiles),
        in_specs=specs + [_smem_q(), _smem_g(), _smem_g()],
        out_specs=pl.BlockSpec((None, 1, bn), lambda iq, ip: (iq, 0, ip)),
        out_shape=jax.ShapeDtypeStruct((q, 1, b), jnp.float32),
        scratch_shapes=[rep],
        interpret=interpret,
        compiler_params=params,
        name="fused_query_scores",
    )(
        *args,
        _per_query_scalar(stop, jnp.int32),
        _as_col(boff, jnp.int32),
        _as_col(n_valid, jnp.int32),
    )
    return jnp.where(live, out.reshape(q, b), jnp.inf)
