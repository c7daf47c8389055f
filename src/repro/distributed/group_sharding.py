"""Sharded big-group serving: one group's rows across the device mesh.

A single table group's ``QueryState`` (codes ``(beta, n)`` + vectors
``(d, n)``, point axis minor) is the unit the serving stack pages, and until this layer it
had to fit one device.  This module makes the row dimension a first-class
mesh axis end to end:

  mesh        ``serving_mesh(n_shards)`` builds the serving mesh with
              ``n_shards`` devices on the "data" axis (a trailing
              size-1 "model" axis keeps the training meshes' two-axis
              layout).  Row placement always goes through the *strict*
              logical-name specs (``distributed.sharding.spec`` with
              ``strict=True``): a row capacity that does not divide the
              mesh is a hard error here, never a silent full replica
              per device.
  state       ``state_shardings`` gives the per-field placement of a
              resident group state — rows over every mesh axis,
              family/scalars replicated.  ``build_group_state_per_host``
              materializes that placement from per-host row ranges
              (``host_row_ranges``) so a huge corpus never exists as one
              host array; ``offload_state_sharded`` /
              ``restore_state_sharded`` page it per shard.
  query       inside the engine's ``shard_map`` both passes run on the
              local row slice through the ordinary kernel dispatch; the
              only cross-shard traffic is ``merge_histograms`` (a psum
              of the (Q, L+2) int level histograms — exact, ints) and
              ``merge_shard_topk`` (all-gather of the k per-shard
              survivors + global re-top-k).  Each shard re-ranks its
              survivors with the exact f32 diff-distance epilogue
              *before* the gather, and ties break by ascending global
              row id on every path, so the merged answer is bit-exact
              with the single-device engine.

``Batcher`` threads ``ServiceConfig.n_shards`` through here (mesh
construction, per-shard paging) and ``IndexConfig.n_shards`` /
``shard_axis`` keep the compiled-step cache key and the paging byte
accounting honest about the per-device slice.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from .sharding import make_mesh, named_sharding

__all__ = [
    "HostShardedState",
    "build_group_state_per_host",
    "host_row_ranges",
    "merge_histograms",
    "merge_shard_topk",
    "offload_state_sharded",
    "restore_state_sharded",
    "serving_mesh",
    "shard_row_offset",
    "state_shardings",
]


def serving_mesh(n_shards: int = 1, *, axis: str = "data") -> Mesh:
    """The serving mesh: ``n_shards`` devices on the row-sharding axis.

    Always a two-axis ``(axis, "model")`` mesh with the model axis at
    size 1, so the serving layer shares the training stack's mesh shape
    conventions and a ``(k, m)`` training mesh drops in unchanged.
    Raises with the ``XLA_FLAGS`` recipe when fewer than ``n_shards``
    devices are visible — on CPU a forced multi-device platform is one
    environment variable away.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    have = jax.device_count()
    if n_shards > have:
        raise ValueError(
            f"n_shards={n_shards} exceeds the {have} visible device(s); "
            f"for a forced multi-device CPU mesh set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_shards}"
        )
    if axis == "model":
        return make_mesh((1, n_shards), ("data", "model"))
    if axis != "data":
        raise ValueError(f"shard axis must be 'data' or 'model', got {axis!r}")
    return make_mesh((n_shards, 1), ("data", "model"))


def state_shardings(mesh: Mesh, cfg):
    """Strict per-field shardings of one group's resident ``QueryState``.

    Row-carrying fields (codes, points) shard their point axis, the
    minor one, over every mesh axis via the "rows" logical-name rule;
    the folded family and the scalars are replicated.  ``strict=True`` is the sharded-serving contract: a row
    capacity that does not divide the mesh raises instead of silently
    replicating the state onto every device (``Batcher.row_capacity``
    rounds capacities to a mesh-size multiple precisely so this never
    fires in the serving path).
    """
    from ..index.engine import QueryState  # deferred: engine imports us

    rows = functools.partial(named_sharding, mesh, (None, "rows"),
                             strict=True)
    return QueryState(
        codes=rows(shape=(cfg.beta, cfg.n)),
        points=rows(shape=(cfg.d, cfg.n)),
        proj=named_sharding(mesh, (None, None)),
        b_int=named_sharding(mesh, (None,)),
        b_frac=named_sharding(mesh, (None,)),
        width=named_sharding(mesh, ()),
        n_valid=named_sharding(mesh, ()),
    )


# ------------------------------------------------------- in-shard collectives


def shard_row_offset(mesh_axes: tuple[str, ...],
                     axis_sizes: tuple[int, ...], n_loc: int):
    """Global row id of this shard's first local row (inside shard_map).

    Rows are laid out major-to-minor in mesh-axis order, so the offset is
    the shard's linearized mesh position times its slice length.  Every
    shard's local candidate indices are rebased by this before any
    cross-shard merge — which is what makes position-based tie-breaks
    equal ascending *global* row id, the same order the single-device
    scan produces.
    """
    off = jnp.int32(0)
    mul = 1
    for ax, size in reversed(tuple(zip(mesh_axes, axis_sizes))):
        off = off + jax.lax.axis_index(ax) * mul
        mul *= size
    return off * n_loc


def merge_histograms(hist_f, hist_g, mesh_axes: tuple[str, ...]):
    """Sum per-shard frequent/good level histograms across the mesh.

    The histograms are int32 counts, so the psum is exact — the merged
    stop condition is bit-identical to evaluating it over the unsharded
    corpus, regardless of shard count or reduction order.
    """
    return (jax.lax.psum(hist_f, mesh_axes),
            jax.lax.psum(hist_g, mesh_axes))


def merge_shard_topk(vals, idx, mesh_axes: tuple[str, ...], k: int):
    """Merge per-shard top-k survivors into the global top-k.

    All-gathers the ``(q, k)`` per-shard candidate distances and global
    row ids (bytes, not rows) and re-top-ks the ``(q, S*k)`` pool.  The
    gathered distances are the shards' exact f32 re-ranked values — no
    arithmetic happens on them here, only selection — so the merged
    ranking is bit-identical to a single device scoring the same rows,
    with distance ties resolved by gather position = ascending shard =
    ascending global row id.
    """
    gv = jax.lax.all_gather(vals, mesh_axes, tiled=False)  # (S, q, k)
    gi = jax.lax.all_gather(idx, mesh_axes, tiled=False)
    s, q = gv.shape[0], gv.shape[1]
    gv = jnp.moveaxis(gv, 0, 1).reshape(q, s * k)
    gi = jnp.moveaxis(gi, 0, 1).reshape(q, s * k)
    fvals, fpos = jax.lax.top_k(-gv, k)
    return -fvals, jnp.take_along_axis(gi, fpos, axis=1)


# ----------------------------------------------------------- per-host build


def host_row_ranges(capacity: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous per-shard row ranges ``[(lo, hi), ...]`` over a capacity.

    The capacity must divide evenly (the same strict contract as
    ``state_shardings``); each range is one shard's slice of the padded
    row space, and a range's tail past the live row count is dead weight
    the build fills deterministically.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if capacity % n_shards:
        raise ValueError(
            f"row capacity {capacity} does not divide {n_shards} shards; "
            f"round the capacity up first (Batcher.row_capacity does)"
        )
    n_loc = capacity // n_shards
    return [(s * n_loc, (s + 1) * n_loc) for s in range(n_shards)]


def _from_row_chunks(mesh: Mesh, chunks: list[np.ndarray],
                     sharding: NamedSharding, dtype) -> jax.Array:
    """Assemble a point-sharded (w, n) device array from per-shard host
    chunks, each (w, n_loc) or its transposed view."""
    n_loc = chunks[0].shape[1]
    shape = (chunks[0].shape[0], n_loc * len(chunks))
    arrs = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        start = idx[1].start or 0
        arrs.append(
            jax.device_put(np.asarray(chunks[start // n_loc], dtype), dev)
        )
    return jax.make_array_from_single_device_arrays(shape, sharding, arrs)


def build_group_state_per_host(
    mesh: Mesh,
    cfg,
    gplan,
    points_loader,
    n_points: int,
):
    """Materialize a sharded ``QueryState`` from per-host row ranges.

    ``points_loader(lo, hi)`` returns the live corpus rows ``[lo, hi)``
    as ``(hi - lo, d)`` float32 — a memmap slice, a file-chunk read, a
    remote fetch — and is called once per shard range, so at no point
    does the full ``(n, d)`` corpus exist as one host array (the per-host
    peak is one shard's slice).  Host-shipped plan codes are row-sliced
    the same way; without them each padded chunk is encoded through the
    jitted f32 build step at the fixed ``(n_loc, d)`` per-device shape —
    the same local matmul the whole-corpus sharded build lowers to — so
    either path is bit-exact with ``build_group_state`` over the
    materialized corpus at the same capacity.
    """
    from ..index import builder  # deferred: builder imports engine

    if not 0 <= n_points <= cfg.n:
        raise ValueError(
            f"n_points={n_points} outside the row capacity [0, {cfg.n}]"
        )
    folded = gplan.folded()
    proj = builder.pad_cols(folded["proj"], cfg.beta)
    b_int = builder.pad_cols(folded["b_int"], cfg.beta)
    b_frac = builder.pad_cols(folded["b_frac"], cfg.beta)
    sh = state_shardings(mesh, cfg)
    vec_dt = jnp.dtype(cfg.vec_dtype)
    encode = None
    codes_chunks: list[np.ndarray] = []
    vec_chunks: list[np.ndarray] = []
    for lo, hi in host_row_ranges(cfg.n, mesh.size):
        n_loc = hi - lo
        m = max(0, min(hi, n_points) - lo)
        pts = np.zeros((n_loc, cfg.d), np.float32)
        if m:
            live = np.ascontiguousarray(
                points_loader(lo, lo + m), np.float32
            )
            if live.shape != (m, cfg.d):
                raise ValueError(
                    f"points_loader({lo}, {lo + m}) returned shape "
                    f"{live.shape}, expected ({m}, {cfg.d})"
                )
            pts[:m] = live
        if gplan.codes is not None:
            cods = np.full((n_loc, cfg.beta), builder._PAD_CODE, np.int32)
            if m:
                cods[:m] = builder.pad_cols(
                    gplan.codes[lo:lo + m], cfg.beta
                ).astype(np.int32)
            # transposed views: the transfer lays out (w, n_loc) itself
            cods, vecs = cods.T, np.asarray(pts, vec_dt).T
        else:
            if encode is None:
                encode = jax.jit(functools.partial(
                    builder._build_fn, vec_dtype=vec_dt
                ))
            cods_d, vecs_d = encode(
                jnp.asarray(pts), jnp.asarray(proj),
                jnp.asarray(b_int), jnp.asarray(b_frac),
            )
            cods, vecs = np.asarray(cods_d), np.asarray(vecs_d)
        codes_chunks.append(cods)
        vec_chunks.append(vecs)

    from ..index.engine import QueryState

    return QueryState(
        codes=_from_row_chunks(mesh, codes_chunks, sh.codes, np.int32),
        points=_from_row_chunks(mesh, vec_chunks, sh.points,
                                np.dtype(vec_dt)),
        proj=jax.device_put(jnp.asarray(proj), sh.proj),
        b_int=jax.device_put(jnp.asarray(b_int), sh.b_int),
        b_frac=jax.device_put(jnp.asarray(b_frac), sh.b_frac),
        width=jax.device_put(jnp.asarray(1.0, jnp.float32), sh.width),
        n_valid=jax.device_put(jnp.asarray(n_points, jnp.int32),
                               sh.n_valid),
    )


# ------------------------------------------------------ per-shard paging


@dataclasses.dataclass
class HostShardedState:
    """Host copy of an evicted sharded group state, one chunk per shard.

    Row-carrying fields are lists of per-shard numpy chunks in global
    row order; the replicated family/scalars are plain arrays.  Keeping
    the shard structure means restore is one upload per shard straight
    to its device — never an all-rows host concatenation — and a
    multi-host deployment only ever holds its own shards.
    """

    codes: list[np.ndarray]
    points: list[np.ndarray]
    proj: np.ndarray
    b_int: np.ndarray
    b_frac: np.ndarray
    width: np.ndarray
    n_valid: np.ndarray


def _row_chunks(arr: jax.Array) -> list[np.ndarray]:
    """Per-shard host copies of a point-sharded array, replicas deduped."""
    by_start: dict[int, np.ndarray] = {}
    for s in arr.addressable_shards:
        start = s.index[1].start or 0
        if start not in by_start:
            by_start[start] = np.asarray(s.data)
    return [by_start[start] for start in sorted(by_start)]


def offload_state_sharded(state) -> HostShardedState:
    """Pull a sharded device state to host, shard by shard, bit-exactly.

    The device-to-host copy happens per addressable shard (replicas
    deduped), so the host footprint mirrors the device layout and the
    chunks carry the exact device bytes — a later
    ``restore_state_sharded`` round-trips them untouched.
    """
    return HostShardedState(
        codes=_row_chunks(state.codes),
        points=_row_chunks(state.points),
        proj=np.asarray(state.proj),
        b_int=np.asarray(state.b_int),
        b_frac=np.asarray(state.b_frac),
        width=np.asarray(state.width),
        n_valid=np.asarray(state.n_valid),
    )


def restore_state_sharded(mesh: Mesh, host: HostShardedState):
    """Upload an ``offload_state_sharded`` copy back onto the mesh.

    Each chunk is ``device_put`` straight to its shard's device and the
    global arrays assembled without any host-side concatenation; the
    restored state is bit-identical to the evicted one (same bytes, same
    placement), so paging a sharded group can never perturb answers.
    """
    from ..index.engine import QueryState

    rows = functools.partial(named_sharding, mesh, (None, "rows"),
                             strict=True)
    n_codes = sum(c.shape[1] for c in host.codes)
    n_pts = sum(c.shape[1] for c in host.points)
    sh_codes = rows(shape=(host.codes[0].shape[0], n_codes))
    sh_pts = rows(shape=(host.points[0].shape[0], n_pts))
    return QueryState(
        codes=_from_row_chunks(mesh, host.codes, sh_codes,
                               host.codes[0].dtype),
        points=_from_row_chunks(mesh, host.points, sh_pts,
                                host.points[0].dtype),
        proj=jax.device_put(host.proj, named_sharding(mesh, (None, None))),
        b_int=jax.device_put(host.b_int, named_sharding(mesh, (None,))),
        b_frac=jax.device_put(host.b_frac, named_sharding(mesh, (None,))),
        width=jax.device_put(host.width, named_sharding(mesh, ())),
        n_valid=jax.device_put(host.n_valid, named_sharding(mesh, ())),
    )
