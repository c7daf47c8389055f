"""Configuration for the distributed WLSH index engine.

An ``IndexConfig`` fixes every compile-relevant shape of one table group's
query step.  Two groups whose configs compare equal lower to the *same*
compiled step — ``shape_signature()`` is the jit-cache key the group-aware
engine uses (see ``engine.QueryStepCache``).  ``pad_beta`` / ``pad_levels``
quantize per-group sizes onto a small set of buckets so a many-group plan
compiles only a handful of distinct steps; per-query ``beta_q`` and
``levels_q`` inputs mask the padding at run time, keeping results exact.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import numpy as np

__all__ = ["IndexConfig", "pad_beta", "pad_levels"]

# Default table-count buckets: multiples of 32 (the relaxed Eq. 11 betas
# land in the tens-to-hundreds, Table 6) capped by powers of two above 512.
_BETA_STEP = 32
_LEVEL_STEP = 4


def _dtype_itemsize(name: str) -> int:
    """Bytes per element of a dtype name, including the ml_dtypes extras."""
    try:
        return np.dtype(name).itemsize
    except TypeError:
        import ml_dtypes  # noqa: F401  (registers bfloat16 etc. with numpy)

        return np.dtype(name).itemsize


def pad_beta(beta: int, buckets: Sequence[int] | None = None) -> int:
    """Smallest admissible table count >= beta (bounds compile count)."""
    if buckets is not None:
        for b in sorted(buckets):
            if b >= beta:
                return int(b)
        raise ValueError(f"beta={beta} exceeds the largest bucket {max(buckets)}")
    if beta <= 512:
        return _BETA_STEP * math.ceil(beta / _BETA_STEP)
    return 1 << math.ceil(math.log2(beta))


def pad_levels(n_levels: int, step: int = _LEVEL_STEP) -> int:
    """Round the compiled level-loop bound up to a multiple of ``step``."""
    return step * math.ceil(max(n_levels, 1) / step)


@dataclasses.dataclass(frozen=True)
class IndexConfig:
    """Shapes + plan parameters for one table group served on a mesh.

    Production-scale defaults correspond to the paper's regime scaled to a
    TPU pod: ~1B points, SIFT-like d, beta from Eq. 11 at n=2^30.
    """

    n: int = 1 << 30  # row capacity (global); streaming builds may reserve
    # capacity above the live row count — state.n_valid masks the tail
    d: int = 128  # dimensions
    beta: int = 128  # hash tables in the group (post-relaxation size)
    q_batch: int = 64  # global query batch
    k: int = 10
    c: int = 2
    n_levels: int = 24  # virtual-rehashing levels (0..n_levels)
    p: float = 2.0
    block_n: int = 1 << 15  # points per scan block (per shard); the per-
    # block scoring working set is ~(q_batch x block_n x beta) x 4 bytes
    # (the XLA-fallback eq-count materializes it) — 1 GB at the production
    # config, next to the 2 GB/chip code shard
    gamma_n: float = 100.0  # gamma * n (paper default gamma = 100/n), so the
    # candidate budget k + ceil(gamma * n) stays aligned with the host
    # planner's PlanConfig regardless of n
    budget_override: int | None = None  # explicit budget; None = derive.
    # At 1B points a larger false-positive budget than the paper's ~k+100
    # is the practical choice — set it here instead of re-deriving gamma.
    vec_dtype: str = "bfloat16"  # stored vectors (verification re-ranks in f32)
    delta_seal_rows: int = 1024  # streaming: an open delta memtable seals
    # into a hashed segment at this row count; not compile-relevant (absent
    # from shape_signature), but part of dataclass equality, so a Batcher
    # threads one uniform value through every group config
    analysis_unroll: bool = False  # unroll block/level loops so the dry-run
    # cost analysis counts true work (XLA counts loop bodies once); used by
    # launch/dryrun.py shallow analysis lowerings only
    n_shards: int = 1  # devices the row capacity is sharded across (the
    # serving mesh size; distributed.group_sharding).  Compile-relevant:
    # the per-shard row slice n/n_shards is the lowered scan extent, so
    # two groups served at different shard counts must not share a step
    shard_axis: str = "data"  # mesh axis name carrying the shards (the
    # trailing "model" axis stays size 1 in serving meshes)

    @property
    def gamma(self) -> float:
        return self.gamma_n / self.n

    @property
    def budget(self) -> int:
        """Candidate budget k + ceil(gamma * n) (paper stop condition 2).

        Computed as ``k + ceil(gamma_n)`` directly: ``gamma * n`` is
        ``gamma_n`` by definition, and the direct form keeps the budget
        exact (and independent of row-capacity padding) where the float
        round-trip ``gamma_n / n * n`` could land on either side of the
        integer.  The host planner computes the same quantity.
        """
        if self.budget_override is not None:
            return self.budget_override
        return self.k + int(math.ceil(self.gamma_n))

    @property
    def state_nbytes(self) -> int:
        """Device bytes of one group's resident ``QueryState`` at this config.

        Accounts every array of the padded state — codes ``(beta, n)`` i32,
        vectors ``(d, n)`` in ``vec_dtype`` (point axis minor), the folded family
        (``proj (d, beta)`` f32, ``b_int``/``b_frac (beta,)``, ``width ()``)
        plus the ``n_valid ()`` row-count scalar — so the serving
        ``StateCache`` can budget residency before a group is ever built.
        Uses the *padded* beta/n_levels/row-capacity shapes (what is
        actually materialized), not the group's raw table or row count.

        With ``n_shards > 1`` this prices the **per-device slice**: row
        arrays shard over the mesh (``n / n_shards`` rows per device,
        strict — never replicated) while the family stays replicated, so
        paging budgets describe what one device actually holds.
        """
        vec_itemsize = _dtype_itemsize(self.vec_dtype)
        per_point = self.beta * 4 + self.d * vec_itemsize
        family = self.d * self.beta * 4 + self.beta * (4 + 4) + 4
        rows_per_shard = -(-self.n // max(self.n_shards, 1))
        return rows_per_shard * per_point + family + 4  # + n_valid scalar

    def shape_signature(self) -> tuple:
        """Everything that determines the compiled query step.

        Frozen + eq dataclass: the config itself is hashable, but the
        explicit tuple documents (and tests pin) what sharing depends on.
        """
        return (
            self.n, self.d, self.beta, self.q_batch, self.k, self.c,
            self.n_levels, self.p, self.block_n, self.budget,
            self.vec_dtype, self.analysis_unroll,
            self.n_shards, self.shard_axis,
        )

    @property
    def width_placeholder(self) -> float:
        return 1.0
