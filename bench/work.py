"""Work a launch of the query step needs, computed from shapes.

``scan_bytes`` is the least any dense-scan implementation must read for
one launch: the launched group's live codes for the widest member in the
batch, and its vectors, once.  The fused kernels re-read the state per
query row and per pass and pad a copy of it, so their true traffic is
higher; a change that stops scanning every row (bucket probing on sorted
codes) must recount this work in a benchmark change first.

``scan_ops`` counts the elementwise integer and float operations of the
two fused passes (level compares and floor divisions per table and
level, and the distance terms per coordinate).  No published peak covers
that int32/VPU mix, so the roofline share is bounded by bytes.
"""

from __future__ import annotations

__all__ = ["scan_bytes", "scan_ops"]


def scan_bytes(rows: int, max_beta: int, d: int, itemsize: int = 4) -> int:
    """Bytes of one read of a group's live codes and vectors."""
    return int(rows) * (4 * int(max_beta) + int(itemsize) * int(d))


def scan_ops(q_batch: int, rows: int, beta: int, n_levels: int,
             d: int) -> int:
    """Elementwise operations of the two passes over one launch."""
    per_row = (int(n_levels) + 1) * 3 * int(beta) + 3 * int(d)
    return 2 * int(q_batch) * int(rows) * per_row
