"""Distributed WLSH table build (the paper's Preprocess on a mesh).

Points are sharded over the point axes; the hash encode is a plain sharded
matmul (rows x replicated projection), so the build is embarrassingly
parallel — XLA emits zero collectives for it.  The group's center weight
and bucket width are *folded* into the projection once so that serving
never touches them:

    proj_folded = diag(W_center) @ A / w
    codes       = floor(x @ proj_folded + b_frac) + b_int

States are stored point axis minor, codes (beta, n) and vectors (d, n)
(``engine.QueryState``).  The device encode transposes inside its own
program.  Host rows (n, w) go to the transfer as their transposed view
(``_put_point_minor``), so the transfer's layout pass, which the chip's
compact layout needs in either orientation, writes the state's layout
directly and no device buffer is held twice.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.families import LpFamilyParams
from ..core.serving_plan import GroupServingPlan
from ..kernels import ref
from .config import IndexConfig
from .engine import QueryState, _point_axes, encode_queries

__all__ = [
    "append_to_state",
    "fold_center_weight",
    "make_build_step",
    "build_state",
    "build_group_state",
    "offload_state",
    "restore_state",
    "pad_cols",
    "build_input_specs",
    "seal_segment",
]

# Row-capacity padding fill for host-code builds: a fixed sentinel code
# and zero vectors.  Dead rows are
# masked out of the query step by ``QueryState.n_valid``, so the fill only
# has to be deterministic — every build path over the same live rows must
# produce bit-identical states.
_PAD_CODE = np.iinfo(np.int32).max // 2


def fold_center_weight(fam: LpFamilyParams) -> dict[str, np.ndarray]:
    """Fold center weight + width into the projection (host-side, once)."""
    proj = fam.proj.astype(np.float64) * fam.center_weight[:, None].astype(
        np.float64
    ) / fam.width
    return dict(
        proj=proj.astype(np.float32),
        b_int=fam.b_int.astype(np.int32),
        b_frac=fam.b_frac.astype(np.float32),
        width=np.float32(1.0),
    )


def _build_fn(points, proj, b_int, b_frac, vec_dtype):
    """(n, d) points -> (beta, n) codes and (d, n) vectors."""
    codes = ref.hash_encode_ref(
        points.astype(jnp.float32),
        proj,
        b_int,
        b_frac,
        jnp.ones((points.shape[1],), jnp.float32),
        1.0,
    )
    return codes.T, points.astype(vec_dtype).T


def _put_point_minor(rows: np.ndarray, sharding, dtype) -> jax.Array:
    """Upload host rows (n, w) as the point-axis-minor (w, n) state field.

    The transfer receives the transposed view and lays it out on the
    device itself, as it already must for the chip's compact layout.
    """
    return jax.device_put(np.asarray(rows, dtype).T, sharding)


def make_build_step(mesh: Mesh, cfg: IndexConfig):
    """jit'd sharded build: (points, proj, b_int, b_frac) -> (codes, vectors),
    points (n, d) in, codes (beta, n) and vectors (d, n) out."""
    pa = _point_axes(mesh)
    rows = NamedSharding(mesh, P(pa, None))
    cols = NamedSharding(mesh, P(None, pa))
    rep2 = NamedSharding(mesh, P(None, None))
    rep1 = NamedSharding(mesh, P(None))
    fn = functools.partial(_build_fn, vec_dtype=jnp.dtype(cfg.vec_dtype))
    return jax.jit(
        fn,
        in_shardings=(rows, rep2, rep1, rep1),
        out_shardings=(cols, cols),
    )


def build_input_specs(cfg: IndexConfig):
    return dict(
        points=jax.ShapeDtypeStruct((cfg.n, cfg.d), jnp.float32),
        proj=jax.ShapeDtypeStruct((cfg.d, cfg.beta), jnp.float32),
        b_int=jax.ShapeDtypeStruct((cfg.beta,), jnp.int32),
        b_frac=jax.ShapeDtypeStruct((cfg.beta,), jnp.float32),
    )


def build_state(
    mesh: Mesh, cfg: IndexConfig, points: np.ndarray, fam: LpFamilyParams
) -> QueryState:
    """Materialize a device-resident QueryState from host data (small/medium
    scale path used by examples/tests; production feeds per-host shards)."""
    folded = fold_center_weight(fam)
    step = make_build_step(mesh, cfg)
    codes, vecs = step(
        jnp.asarray(points, jnp.float32),
        jnp.asarray(folded["proj"]),
        jnp.asarray(folded["b_int"]),
        jnp.asarray(folded["b_frac"]),
    )
    rep2 = NamedSharding(mesh, P(None, None))
    rep1 = NamedSharding(mesh, P(None))
    rep0 = NamedSharding(mesh, P())
    return QueryState(
        codes=codes,
        points=vecs,
        proj=jax.device_put(jnp.asarray(folded["proj"]), rep2),
        b_int=jax.device_put(jnp.asarray(folded["b_int"]), rep1),
        b_frac=jax.device_put(jnp.asarray(folded["b_frac"]), rep1),
        width=jax.device_put(jnp.asarray(1.0, jnp.float32), rep0),
        n_valid=jax.device_put(jnp.asarray(len(points), jnp.int32), rep0),
    )


def _state_shardings(mesh: Mesh) -> QueryState:
    """Per-field shardings of a resident QueryState (points over all axes)."""
    pa = _point_axes(mesh)
    return QueryState(
        codes=NamedSharding(mesh, P(None, pa)),
        points=NamedSharding(mesh, P(None, pa)),
        proj=NamedSharding(mesh, P(None, None)),
        b_int=NamedSharding(mesh, P(None)),
        b_frac=NamedSharding(mesh, P(None)),
        width=NamedSharding(mesh, P()),
        n_valid=NamedSharding(mesh, P()),
    )


def offload_state(state: QueryState) -> QueryState:
    """Pull a device QueryState into host memory, bit-exactly.

    The host copy is a plain-numpy QueryState (codes keep int32, vectors
    keep ``vec_dtype`` — bfloat16 arrays come back as ml_dtypes numpy
    arrays), so a later ``restore_state`` round-trips the exact device
    bytes: candidate sets and answers are unchanged across an
    evict/restore cycle.  Dropping the returned value's device-side
    ancestor frees the group's device footprint.
    """
    return QueryState(
        **{
            f.name: np.asarray(getattr(state, f.name))
            for f in dataclasses.fields(QueryState)
        }
    )


def restore_state(mesh: Mesh, host: QueryState) -> QueryState:
    """Upload an ``offload_state`` host copy back onto the mesh.

    A pure ``device_put`` per field with the build-time shardings — no
    re-encode, no recompile — so restore cost is one host-to-device copy
    of ``IndexConfig.state_nbytes`` bytes and the restored state is
    bit-identical to the evicted one.
    """
    sh = _state_shardings(mesh)
    return QueryState(
        **{
            f.name: jax.device_put(getattr(host, f.name), getattr(sh, f.name))
            for f in dataclasses.fields(QueryState)
        }
    )


def pad_cols(x: np.ndarray, beta: int) -> np.ndarray:
    """Pad the trailing (table) axis to ``beta`` columns with zeros.

    Padded tables are dead weight only: every query masks lanes >= its
    beta_q in freq_level, and beta_q never exceeds the group's real beta.
    """
    have = x.shape[-1]
    if have == beta:
        return x
    if have > beta:
        raise ValueError(f"group beta {have} exceeds padded config beta {beta}")
    pad = [(0, 0)] * (x.ndim - 1) + [(0, beta - have)]
    return np.pad(x, pad)


def build_group_state(
    mesh: Mesh,
    cfg: IndexConfig,
    points: np.ndarray | None,
    gplan: GroupServingPlan,
    *,
    extra_points: np.ndarray | None = None,
    extra_codes: np.ndarray | None = None,
    base_rows: np.ndarray | None = None,
    points_loader=None,
    n_points: int | None = None,
) -> QueryState:
    """Materialize one table group's QueryState from its serving plan.

    ``cfg.beta`` may exceed the group's real table count (bucketed shape
    padding, config.pad_beta); family params are zero-padded to match.  When
    the plan ships host-computed codes they are placed directly (bit-exact
    candidate sets vs the host oracle); otherwise the codes are built on
    device through the sharded encode.

    Streaming extensions:

    * ``cfg.n`` is a row *capacity* and may exceed the live row count;
      excess rows are deterministic dead weight (sentinel codes / zero
      vectors on the host-code path, encoded zero vectors on the device
      path) masked out of every query by ``QueryState.n_valid``.
    * ``extra_points`` appends already-compacted streaming rows after the
      base corpus (the cold-rebuild path for a group that has absorbed
      delta segments); ``extra_codes`` carries their sealed hash codes on
      the host-code path (``seal_segment`` output, already at ``cfg.beta``
      columns).  The result is bit-exact with a state that reached the
      same rows through ``append_to_state``.
    * ``base_rows`` restricts the base corpus to those row indices (in
      that order) before the extra rows are appended — the tombstone-purge
      rebuild path: purged rows simply never enter the state, and the
      plan's host codes are row-sliced to match.  None keeps every row.
    * ``points_loader`` + ``n_points`` replace ``points`` (pass None)
      with per-host row ranges: ``points_loader(lo, hi)`` yields just the
      rows one shard needs, so a huge corpus never materializes on one
      host (``distributed.group_sharding.build_group_state_per_host``).
      Bit-exact with the materialized path at the same capacity; the
      streaming kwargs don't combine with it (delta compaction rebuilds
      from the materialized corpus).
    """
    if points_loader is not None:
        if points is not None:
            raise ValueError(
                "pass either points or points_loader, not both"
            )
        if n_points is None:
            raise ValueError("points_loader requires n_points")
        if (extra_points is not None or extra_codes is not None
                or base_rows is not None):
            raise ValueError(
                "points_loader does not combine with the streaming "
                "kwargs (extra_points/extra_codes/base_rows)"
            )
        from ..distributed.group_sharding import build_group_state_per_host

        return build_group_state_per_host(
            mesh, cfg, gplan, points_loader, n_points
        )
    folded = gplan.folded()
    proj = pad_cols(folded["proj"], cfg.beta)
    b_int = pad_cols(folded["b_int"], cfg.beta)
    b_frac = pad_cols(folded["b_frac"], cfg.beta)
    pa = _point_axes(mesh)
    cols = NamedSharding(mesh, P(None, pa))
    rep2 = NamedSharding(mesh, P(None, None))
    rep1 = NamedSharding(mesh, P(None))
    rep0 = NamedSharding(mesh, P())

    points = np.ascontiguousarray(points, dtype=np.float32)
    if base_rows is not None:
        base_rows = np.asarray(base_rows, np.int64)
        points = np.ascontiguousarray(points[base_rows])
    if extra_points is not None and len(extra_points):
        extra_points = np.ascontiguousarray(extra_points, dtype=np.float32)
        points = np.concatenate([points, extra_points], axis=0)
    n_rows = len(points)
    if n_rows > cfg.n:
        raise ValueError(
            f"{n_rows} live rows exceed the config row capacity {cfg.n}"
        )
    pad_rows = cfg.n - n_rows

    if gplan.codes is not None:
        base_codes = gplan.codes
        if base_rows is not None:
            base_codes = base_codes[base_rows]
        codes_np = pad_cols(base_codes, cfg.beta).astype(np.int32)
        if extra_codes is not None and len(extra_codes):
            if extra_codes.shape[1] != cfg.beta:
                raise ValueError(
                    f"extra_codes must be sealed at cfg.beta={cfg.beta} "
                    f"columns, got {extra_codes.shape[1]}"
                )
            codes_np = np.concatenate(
                [codes_np, extra_codes.astype(np.int32)], axis=0
            )
        if len(codes_np) != n_rows:
            raise ValueError(
                f"host codes cover {len(codes_np)} rows, expected {n_rows} "
                f"(pass extra_codes alongside extra_points)"
            )
        if pad_rows:
            codes_np = np.concatenate([
                codes_np,
                np.full((pad_rows, cfg.beta), _PAD_CODE, np.int32),
            ], axis=0)
            points = np.concatenate([
                points, np.zeros((pad_rows, cfg.d), np.float32)
            ], axis=0)
        codes = _put_point_minor(codes_np, cols, np.int32)
        vecs = _put_point_minor(points, cols, jnp.dtype(cfg.vec_dtype))
    else:
        if pad_rows:
            points = np.concatenate([
                points, np.zeros((pad_rows, cfg.d), np.float32)
            ], axis=0)
        step = make_build_step(mesh, cfg)
        codes, vecs = step(
            jnp.asarray(points, jnp.float32),
            jnp.asarray(proj),
            jnp.asarray(b_int),
            jnp.asarray(b_frac),
        )
    return QueryState(
        codes=codes,
        points=vecs,
        proj=jax.device_put(jnp.asarray(proj), rep2),
        b_int=jax.device_put(jnp.asarray(b_int), rep1),
        b_frac=jax.device_put(jnp.asarray(b_frac), rep1),
        width=jax.device_put(jnp.asarray(1.0, jnp.float32), rep0),
        n_valid=jax.device_put(jnp.asarray(n_rows, jnp.int32), rep0),
    )


def seal_segment(
    cfg: IndexConfig,
    gplan: GroupServingPlan,
    vectors: np.ndarray,
    state: QueryState | None = None,
) -> np.ndarray:
    """Hash a delta segment into ``(m, cfg.beta)`` int32 bucket codes.

    Re-hashes the segment's rows with the group's *original* family seeds,
    through the same encoding the group's data codes used: the host f64
    path when the plan ships host codes (bit-exact with a fresh host build
    over the union corpus), otherwise the device f32 path via the state's
    folded projection (``state`` required).  Sealed codes are what
    ``append_to_state`` later splices into the main group state — the
    hashing work of compaction happens here, at seal time.
    """
    vectors = np.ascontiguousarray(np.atleast_2d(vectors), np.float32)
    if gplan.codes is not None:
        return pad_cols(
            gplan.encode_host(vectors), cfg.beta
        ).astype(np.int32)
    if state is None:
        raise ValueError(
            "sealing without plan host codes requires the group's device "
            "state for the f32 encode"
        )
    return np.asarray(encode_queries(state, vectors), np.int32)


def append_to_state(
    state: QueryState,
    codes: np.ndarray,
    vectors: np.ndarray,
    mesh: Mesh | None = None,
) -> QueryState:
    """Splice sealed rows into a group state's reserved capacity.

    Writes ``m`` new rows (columns of the point-axis-minor state) at
    ``state.n_valid`` and returns a state with
    ``n_valid`` advanced — codes/vector buffers keep their compiled
    (capacity) shapes, so the compaction that calls this never triggers a
    query-step recompile.  The update is functional (the input state stays
    valid; the transient extra copy of one group is the compaction cost);
    with ``mesh`` the result is re-placed onto the build-time shardings.
    Bit-exact with ``build_group_state`` over the union corpus at the same
    capacity.
    """
    m = len(codes)
    if m != len(vectors):
        raise ValueError(f"codes/vectors row mismatch: {m} vs {len(vectors)}")
    off = int(state.n_valid)
    cap = state.codes.shape[1]
    if off + m > cap:
        raise ValueError(
            f"append of {m} rows at {off} exceeds row capacity {cap} "
            f"(raise ServiceConfig.delta_reserve_rows)"
        )
    codes_d = jnp.asarray(np.asarray(codes, np.int32).T)
    vecs_d = jnp.asarray(np.asarray(vectors, state.points.dtype).T)
    new_codes = jax.lax.dynamic_update_slice(state.codes, codes_d, (0, off))
    new_points = jax.lax.dynamic_update_slice(state.points, vecs_d, (0, off))
    n_valid = jnp.asarray(off + m, jnp.int32)
    if mesh is not None:
        sh = _state_shardings(mesh)
        new_codes = jax.device_put(new_codes, sh.codes)
        new_points = jax.device_put(new_points, sh.points)
        n_valid = jax.device_put(n_valid, sh.n_valid)
    return dataclasses.replace(
        state, codes=new_codes, points=new_points, n_valid=n_valid
    )
