"""Sharded WLSH query engine (the paper's Search).

A group's state is stored point axis minor: codes (beta, n) and vectors
(d, n), with the row capacity n in whole 256-row kernel tiles on every
shard (``Batcher.row_capacity``).  This is the chip's own compact layout
for such arrays: the tables and dimensions go on sublanes and the points
fill the 128 lanes, so HBM holds no more than the live rows padded to a
tile, and the Pallas kernels read their (beta, 256) / (d, 256) tiles in
place.  Stored the other way, (n, beta) with beta not a multiple of 128,
the chip keeps the point axis minor anyway and every launch would
transpose and pad the whole state into the kernels' tiles.

The point axis is sharded over every mesh axis, and the query batch is
replicated.  Each device scores all Q queries against its n/devices
rows, so the (Q, n) work splits by rows while per-device state stays
1/devices of the index.  The only communication is

  * a psum of per-query level histograms, (Q, L+2) ints, and
  * an all-gather of per-shard top-k rows, (Q, k),

both over all axes.  Per shard the engine streams its code/vector slabs
through VMEM-sized blocks in two passes (lax.scan):

  pass 1  codes + vectors -> first-frequent level, distance -> per-level
          frequent/good histograms -> psum -> the paper's stop conditions
          (k found / budget) -> j*
  pass 2  codes + vectors -> masked distances (L_freq <= j*) -> running
          local top-k -> exact re-rank -> all-gather -> global top-k

Each scan step of both passes is one ``ops.fused_query_block`` call, which
computes level, distance and histogram/mask together, so the (q_loc,
block) intermediates never round-trip through HBM between stages.  The
backend alone picks its kernel (``kernels.platform.scan_mode``): the
Pallas kernels on TPU, the fused XLA composite in ref.py elsewhere.

Pass 2 recomputes L_freq instead of materializing the (Q, n_loc) level
matrix, trading compute for HBM footprint.

Every query carries its own weight vector, collision threshold mu, radius
base r_min, table count beta_q and level cap levels_q (the WLSH multi-weight
semantics -- queries under *different* weighted distance functions batch
together as long as they hit the same table group).  Query bucket codes are
an *input*: the retrieval service encodes on the host (float64, bit-exact
against the planner's codes) while standalone callers use
``encode_queries``.  Per-query beta_q/levels_q also make shape padding
exact, so groups whose (beta, n_levels) round to the same buckets share one
compiled step via ``QueryStepCache``.  The batch's live-row count
``n_live`` is a run-time input too: the Pallas kernels run no grid step
for the padding rows past it, with no new compiled shape.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed import group_sharding
from ..distributed.sharding import shard_map_nocheck
from ..kernels import ops, ref
from .config import IndexConfig

__all__ = [
    "QueryState",
    "QueryStepCache",
    "encode_queries",
    "make_query_step",
    "query_input_specs",
    "shardings",
]


@dataclasses.dataclass(frozen=True)
class QueryState:
    """Device-resident table-group state (a pytree).

    ``codes``/``points`` are stored point axis minor, (beta, n) and
    (d, n), the layout the chip keeps and the kernels tile in place (see
    the module docstring).  They are materialized at the config's row
    *capacity* (``IndexConfig.n``); ``n_valid`` counts the live rows.  Rows at or
    beyond ``n_valid`` are dead weight the query step masks out of both
    histogram passes, which is what lets streaming compaction append rows
    into reserved capacity without changing any compiled shape.  A static
    (non-streaming) build simply has ``n_valid == capacity``.
    """

    codes: jax.Array  # (beta, n) int32, points sharded over every axis
    points: jax.Array  # (d, n) vec_dtype, sharded likewise
    proj: jax.Array  # (d, beta) f32, replicated
    b_int: jax.Array  # (beta,) int32, replicated
    b_frac: jax.Array  # (beta,) f32, replicated
    width: jax.Array  # () f32
    n_valid: jax.Array  # () int32, replicated — live rows in [0, n]


jax.tree_util.register_dataclass(
    QueryState,
    data_fields=["codes", "points", "proj", "b_int", "b_frac", "width",
                 "n_valid"],
    meta_fields=[],
)


def _point_axes(mesh: Mesh):
    """Point rows shard over every mesh axis (see module docstring)."""
    return tuple(mesh.axis_names)


def shardings(mesh: Mesh):
    pa = _point_axes(mesh)
    return {
        "state": QueryState(
            codes=NamedSharding(mesh, P(None, pa)),
            points=NamedSharding(mesh, P(None, pa)),
            proj=NamedSharding(mesh, P(None, None)),
            b_int=NamedSharding(mesh, P(None)),
            b_frac=NamedSharding(mesh, P(None)),
            width=NamedSharding(mesh, P()),
            n_valid=NamedSharding(mesh, P()),
        ),
        "queries": NamedSharding(mesh, P(None, None)),
        "q_meta": NamedSharding(mesh, P(None)),
        "scalar": NamedSharding(mesh, P()),
        "out": NamedSharding(mesh, P(None, None)),
    }


def _query_shard(
    state: QueryState,
    queries,  # (q_loc, d)
    codes_q,  # (q_loc, beta) int32 precomputed query bucket codes
    q_weight,  # (q_loc, d)
    mu,  # (q_loc,) int32
    r_min,  # (q_loc,) f32
    beta_q,  # (q_loc,) int32 per-member beta_{W_i}
    levels_q,  # (q_loc,) int32 per-member level cap (<= cfg.n_levels)
    n_live,  # () int32 live query rows; the rest repeat them as padding
    cfg: IndexConfig,
    mesh_axes: tuple[str, ...],
    axis_sizes: tuple[int, ...],
):
    c, L, k = cfg.c, cfg.n_levels, cfg.k
    n_loc = state.codes.shape[1]
    block = min(cfg.block_n, n_loc)
    if n_loc % block:
        raise ValueError(f"block_n {block} does not divide the shard's "
                         f"{n_loc} rows; the tail would never be scanned")
    n_blocks = n_loc // block
    q_loc = queries.shape[0]
    qf32 = queries.astype(jnp.float32)
    wf32 = q_weight.astype(jnp.float32)

    # Global row offsets per block: streaming states reserve row capacity
    # above the live count, and rows >= n_valid must vanish from both
    # passes (the block step excludes them from histograms and scores).
    shard_off = group_sharding.shard_row_offset(mesh_axes, axis_sizes, n_loc)
    boffs = shard_off + jnp.arange(n_blocks, dtype=jnp.int32) * block

    def blocks(boff):
        """The block's (beta, block) codes and (d, block) vectors, sliced
        along the point axis; one block is the whole shard, untouched."""
        off = boff - shard_off
        return (jax.lax.dynamic_slice_in_dim(state.codes, off, block, 1),
                jax.lax.dynamic_slice_in_dim(state.points, off, block, 1))

    n_valid = state.n_valid.astype(jnp.int32)
    block_step = functools.partial(
        ops.fused_query_block, codes_q=codes_q, queries=qf32,
        q_weight=wf32, mu=mu, r_min=r_min, beta_q=beta_q, n_valid=n_valid,
        c=c, n_levels=L, p=cfg.p, n_live=n_live, unroll=cfg.analysis_unroll,
    )

    # ---- pass 1: level histograms -> stop level ---------------------------
    def pass1(carry, boff):
        hist_f, hist_g = carry
        hf, hg = block_step(*blocks(boff), boff=boff)
        return (hist_f + hf, hist_g + hg), None

    hist0 = jnp.zeros((q_loc, L + 2), jnp.int32)
    (hist_f, hist_g), _ = jax.lax.scan(
        pass1, (hist0, hist0), boffs,
        unroll=n_blocks if cfg.analysis_unroll else 1,
    )
    hist_f, hist_g = group_sharding.merge_histograms(hist_f, hist_g,
                                                     mesh_axes)
    nf_cum = jnp.cumsum(hist_f[:, : L + 1], axis=1)
    ng_cum = jnp.cumsum(hist_g[:, : L + 1], axis=1)
    # Stop conditions evaluated only up to each query's own level cap: the
    # compiled bound L may be padded above the member's n_levels (bucketed
    # shape sharing), and a query that exhausts its levels stops *at* them
    # exactly like the host loop.
    levels = jnp.arange(L + 1, dtype=jnp.int32)
    cond = ((ng_cum >= k) | (nf_cum >= cfg.budget)) & (
        levels[None, :] <= levels_q[:, None]
    )
    stop = jnp.where(
        jnp.any(cond, axis=1), jnp.argmax(cond, axis=1), levels_q
    ).astype(jnp.int32)  # (q_loc,)

    # ---- pass 2: masked distances -> running local top-k ------------------
    def pass2(carry, boff):
        vals, idx = carry
        scores = block_step(*blocks(boff), boff=boff, stop=stop)
        bvals, bidx = jax.lax.top_k(-scores, k)
        bidx = bidx + boff
        vals = jnp.concatenate([vals, -bvals], axis=1)
        idx = jnp.concatenate([idx, bidx], axis=1)
        mvals, mpos = jax.lax.top_k(-vals, k)
        return (-mvals, jnp.take_along_axis(idx, mpos, axis=1)), None

    init = (
        jnp.full((q_loc, k), jnp.inf, jnp.float32),
        jnp.full((q_loc, k), -1, jnp.int32),
    )
    (vals, idx), _ = jax.lax.scan(
        pass2, init, boffs,
        unroll=n_blocks if cfg.analysis_unroll else 1,
    )

    # ---- exact re-rank of the k local winners ------------------------------
    # The p=2 scan scores with the norms+matmul expansion (MXU); its f32
    # cancellation error is ~|x||ulp| — swamping genuinely small distances.
    # Recompute the survivors' distances from the coordinate differences
    # ((q_loc, k, d) work, exact in f32) and re-sort.
    local_rows = jnp.clip(idx - shard_off, 0, n_loc - 1)
    cand = ops.gather_columns(state.points, local_rows,
                              n_live=n_live).astype(jnp.float32)
    diff = jnp.abs((qf32[:, None, :] - cand) * wf32[:, None, :])
    if abs(cfg.p - 2.0) < 1e-9:
        exact = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
    elif abs(cfg.p - 1.0) < 1e-9:
        exact = jnp.sum(diff, axis=-1)
    else:
        exact = jnp.sum(diff**cfg.p, axis=-1) ** (1.0 / cfg.p)
    vals = jnp.where(jnp.isfinite(vals), exact, vals)
    rvals, rpos = jax.lax.top_k(-vals, k)
    vals = -rvals
    idx = jnp.take_along_axis(idx, rpos, axis=1)

    # ---- global top-k merge ------------------------------------------------
    fvals, fidx = group_sharding.merge_shard_topk(vals, idx, mesh_axes, k)
    n_checked = jnp.minimum(
        jnp.take_along_axis(nf_cum, stop[:, None], axis=1)[:, 0],
        jnp.int32(cfg.budget),
    )
    return fvals, fidx, stop, n_checked


def encode_queries(state: QueryState, queries) -> jax.Array:
    """(Q, beta) int32 query bucket codes via the device (f32) path.

    state.proj is the *folded* projection (center weight and bucket width
    folded in at build time), so queries hash with unit weight/width.  The
    retrieval service instead host-encodes in float64 for bit-exactness
    against the planner; this is the standalone/engine-only path.
    """
    return ref.hash_encode_ref(
        jnp.asarray(queries, jnp.float32),
        state.proj,
        state.b_int,
        state.b_frac,
        jnp.ones((state.proj.shape[0],), jnp.float32),
        1.0,
    )


def make_query_step(mesh: Mesh, cfg: IndexConfig):
    """jit'd sharded query step:
    (state, queries, q_codes, q_weight, mu, r_min, beta_q, levels_q, n_live)
    -> (dists (Q,k), ids (Q,k), stop (Q,), n_checked (Q,)).

    ``n_live`` (() int32, replicated) counts the batch's live rows; rows
    at or past it are padding, and the Pallas kernels skip them (their
    outputs are defined but not answers).  A full batch passes Q."""
    pa = _point_axes(mesh)
    sh = shardings(mesh)
    # Strict row placement (distributed.group_sharding): a capacity that
    # does not divide the mesh raises here instead of silently replicating
    # the state onto every device.
    state_sh = group_sharding.state_shardings(mesh, cfg)

    fn = functools.partial(
        _query_shard, cfg=cfg, mesh_axes=pa,
        axis_sizes=tuple(mesh.shape[a] for a in pa),
    )
    smapped = shard_map_nocheck(
        fn,
        mesh=mesh,
        in_specs=(
            QueryState(
                codes=P(None, pa),
                points=P(None, pa),
                proj=P(None, None),
                b_int=P(None),
                b_frac=P(None),
                width=P(),
                n_valid=P(),
            ),
            P(None, None),
            P(None, None),
            P(None, None),
            P(None),
            P(None),
            P(None),
            P(None),
            P(),
        ),
        out_specs=(P(None, None), P(None, None), P(None), P(None)),
    )
    return jax.jit(
        smapped,
        in_shardings=(
            state_sh,
            sh["queries"],
            sh["queries"],
            sh["queries"],
            sh["q_meta"],
            sh["q_meta"],
            sh["q_meta"],
            sh["q_meta"],
            sh["scalar"],
        ),
        out_shardings=(sh["out"], sh["out"], sh["q_meta"], sh["q_meta"]),
    )


class QueryStepCache:
    """Compiled-step reuse across table groups.

    Keyed by (mesh, cfg): IndexConfig is a frozen eq dataclass, so two
    groups whose shapes quantize to the same buckets (config.pad_beta /
    pad_levels) produce equal configs and share one lowered+compiled step.
    ``n_compiled`` counts actual make_query_step calls — the serving tests
    pin it to the number of distinct shape signatures.  ``on_compile``
    (optional, set by the observability layer) is called with the config
    on every cache miss, attributing compiles to shape signatures.
    """

    def __init__(self):
        self._steps: dict = {}
        self.n_compiled = 0
        self.on_compile = None  # hook: on_compile(cfg) per actual compile

    def get(self, mesh: Mesh, cfg: IndexConfig):
        key = (mesh, cfg)
        step = self._steps.get(key)
        if step is None:
            step = make_query_step(mesh, cfg)
            self._steps[key] = step
            self.n_compiled += 1
            if self.on_compile is not None:
                self.on_compile(cfg)
        return step

    def __len__(self) -> int:
        return len(self._steps)


def query_input_specs(cfg: IndexConfig):
    """ShapeDtypeStructs for the dry-run (no allocation)."""
    vec = jnp.dtype(cfg.vec_dtype)
    state = QueryState(
        codes=jax.ShapeDtypeStruct((cfg.beta, cfg.n), jnp.int32),
        points=jax.ShapeDtypeStruct((cfg.d, cfg.n), vec),
        proj=jax.ShapeDtypeStruct((cfg.d, cfg.beta), jnp.float32),
        b_int=jax.ShapeDtypeStruct((cfg.beta,), jnp.int32),
        b_frac=jax.ShapeDtypeStruct((cfg.beta,), jnp.float32),
        width=jax.ShapeDtypeStruct((), jnp.float32),
        n_valid=jax.ShapeDtypeStruct((), jnp.int32),
    )
    return dict(
        state=state,
        queries=jax.ShapeDtypeStruct((cfg.q_batch, cfg.d), jnp.float32),
        q_codes=jax.ShapeDtypeStruct((cfg.q_batch, cfg.beta), jnp.int32),
        q_weight=jax.ShapeDtypeStruct((cfg.q_batch, cfg.d), jnp.float32),
        mu=jax.ShapeDtypeStruct((cfg.q_batch,), jnp.int32),
        r_min=jax.ShapeDtypeStruct((cfg.q_batch,), jnp.float32),
        beta_q=jax.ShapeDtypeStruct((cfg.q_batch,), jnp.int32),
        levels_q=jax.ShapeDtypeStruct((cfg.q_batch,), jnp.int32),
        n_live=jax.ShapeDtypeStruct((), jnp.int32),
    )
