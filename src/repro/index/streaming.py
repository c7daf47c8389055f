"""Streaming-insert primitives: delta memtables, sealed segments, exact scan.

The WLSH group states are compiled at fixed shapes, so fresh inserts
cannot enter them row-by-row.  Instead each table group carries a small
mutable side-structure with an LSM-like lifecycle:

  open      an ``DeltaSegment`` memtable accumulates raw inserted vectors;
            queries scan it *exactly* (full weighted l_p distance, the
            same coordinate-difference form the engine's re-rank epilogue
            uses), so recall on unsealed points is perfect by construction
  sealed    at ``IndexConfig.delta_seal_rows`` rows the memtable freezes
            into a ``SealedSegment``: its rows re-hashed with the group's
            original family seeds (``builder.seal_segment``) into a hashed
            mini-state that still serves by exact scan but is ready to
            splice into the main state
  compacted ``builder.append_to_state`` moves sealed rows into the group
            state's reserved row capacity — after which they are served by
            the compiled index path, bit-exact with a fresh build over the
            union corpus

This module owns the host-side data structures and the exact-scan math;
the serving-layer orchestration (routing, tombstones, the compaction
transaction against the ``StateCache``) lives in ``repro.serving.delta``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["DeltaSegment", "SealedSegment", "exact_weighted_lp", "scan_topk"]


class DeltaSegment:
    """Append-only open memtable of one group's unsealed inserts."""

    def __init__(self, d: int):
        self.d = int(d)
        self._ids: list[int] = []
        self._vecs: list[np.ndarray] = []
        self._stacked: np.ndarray | None = None  # cached ``vectors`` view

    def __len__(self) -> int:
        """Number of unsealed rows currently buffered."""
        return len(self._ids)

    def append(self, point_id: int, vector: np.ndarray) -> None:
        """Buffer one inserted vector under its assigned global id."""
        vector = np.ascontiguousarray(vector, np.float32).reshape(-1)
        if vector.shape != (self.d,):
            raise ValueError(
                f"insert must be a ({self.d},) vector, got {vector.shape}"
            )
        self._ids.append(int(point_id))
        self._vecs.append(vector)
        self._stacked = None  # invalidate the cached stack

    @property
    def ids(self) -> np.ndarray:
        """(m,) int64 global point ids of the buffered rows."""
        return np.asarray(self._ids, np.int64)

    @property
    def vectors(self) -> np.ndarray:
        """(m, d) float32 buffered rows, in insertion order.

        The stacked array is cached between writes: every query routed to
        a group scans its pending rows, so re-stacking per read would put
        an O(m*d) host copy on the query hot path.  The cache is
        invalidated by ``append``/``drain`` and returned read-only (it is
        shared across reads — callers copy before mutating, which the
        exact-scan path never does).
        """
        if self._stacked is None:
            if self._vecs:
                stacked = np.stack(self._vecs).astype(np.float32)
            else:
                stacked = np.empty((0, self.d), np.float32)
            stacked.flags.writeable = False
            self._stacked = stacked
        return self._stacked

    def drain(self) -> tuple[np.ndarray, np.ndarray]:
        """Freeze and clear the memtable, returning ``(ids, vectors)``."""
        ids, vecs = self.ids, self.vectors
        self._ids, self._vecs = [], []
        self._stacked = None
        return ids, vecs


@dataclasses.dataclass(frozen=True)
class SealedSegment:
    """An immutable hashed mini-state awaiting compaction.

    ``codes`` are the rows re-hashed with the owning group's original
    family seeds at the group's padded table width (``seal_segment``), so
    compaction is a pure splice — no hashing happens on the compaction
    path itself.
    """

    ids: np.ndarray  # (m,) int64 global point ids
    vectors: np.ndarray  # (m, d) float32
    codes: np.ndarray  # (m, beta_padded) int32

    def __len__(self) -> int:
        """Number of rows in the sealed segment."""
        return len(self.ids)


def exact_weighted_lp(
    queries: np.ndarray,
    vectors: np.ndarray,
    q_weights: np.ndarray,
    p: float,
) -> np.ndarray:
    """(Q, m) exact per-query weighted l_p distances, float32.

    Coordinate-difference form — the same epilogue the sharded engine
    re-ranks its top-k survivors with, *not* the norms+matmul
    expansion whose f32 cancellation error swamps small distances.  Delta
    hits therefore rank against indexed hits on equal footing.
    """
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    q_weights = np.atleast_2d(np.asarray(q_weights, np.float32))
    vectors = np.atleast_2d(np.asarray(vectors, np.float32))
    diff = np.abs(
        (queries[:, None, :] - vectors[None, :, :]) * q_weights[:, None, :]
    ).astype(np.float32)
    if abs(p - 2.0) < 1e-9:
        return np.sqrt(np.sum(diff * diff, axis=-1, dtype=np.float32))
    if abs(p - 1.0) < 1e-9:
        return np.sum(diff, axis=-1, dtype=np.float32)
    return (
        np.sum(diff**np.float32(p), axis=-1, dtype=np.float32)
        ** np.float32(1.0 / p)
    )


def scan_topk(
    queries: np.ndarray,
    q_weights: np.ndarray,
    ids: np.ndarray,
    vectors: np.ndarray,
    p: float,
    k: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k of the delta rows per query: ``(ids, dists)`` (Q, k).

    Missing slots (fewer than ``k`` delta rows) hold id -1 / distance
    +inf, the same conventions the engine uses, so the batching layer's
    merge treats delta hits and indexed hits uniformly.  Ties sort by
    insertion order.

    Selection runs in O(m) per query via ``np.argpartition`` on a
    composite ``(distance bits, row index)`` key — bit-identical to a
    full stable argsort of the distance matrix (the distances are
    non-negative float32, so their bit patterns order like the values,
    and the packed row index breaks ties by insertion order exactly as
    a stable sort would), without the O(m log m) sort over rows that
    can never reach the top-k.
    """
    queries = np.atleast_2d(np.asarray(queries, np.float32))
    nq = len(queries)
    out_ids = np.full((nq, k), -1, np.int64)
    out_d = np.full((nq, k), np.inf, np.float32)
    m = len(ids)
    if m == 0:
        return out_ids, out_d
    dists = exact_weighted_lp(queries, vectors, q_weights, p)
    take = min(k, m)
    # + 0.0 normalizes any -0.0 so the uint32 bit pattern is monotone
    keys = (dists + np.float32(0.0)).view(np.uint32).astype(np.int64)
    keys = (keys << np.int64(32)) | np.arange(m, dtype=np.int64)[None, :]
    if take < m:
        part = np.argpartition(keys, take - 1, axis=1)[:, :take]
        sel = np.take_along_axis(keys, part, axis=1)
        order = np.take_along_axis(part, np.argsort(sel, axis=1), axis=1)
    else:
        order = np.argsort(keys, axis=1)
    out_ids[:, :take] = np.asarray(ids, np.int64)[order]
    out_d[:, :take] = np.take_along_axis(dists, order, axis=1)
    return out_ids, out_d
