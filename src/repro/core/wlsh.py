"""WLSH index: Preprocess (Algorithm 1) + Search (Algorithm 2).

This module is the *paper-faithful* host implementation (numpy): hash tables
are per-function sorted code arrays, built on the first host search of a
group (the serving-plan export ships only the raw codes and never builds
them); the search runs the C2LSH virtual-rehashing level loop with
incremental collision counting, so its work (and the I/O metric we report)
is proportional to the buckets actually probed — exactly the quantity the
paper's experiments measure.

The TPU-dense formulation (single-pass L_freq order statistic, Pallas
kernels, sharded execution) lives in ``repro.index`` / ``repro.kernels`` and
is cross-validated against this implementation in tests.

Glossary against the paper:
  * group            = S_i in the partition (one physical table group)
  * plan.betas/mus   = beta_{W_i}, mu_{W_i} from Eqs. 11-12
  * level j          = radius R = r_min^{W_i} * c^j, bucket = floor(h / c^j)
  * stop conditions  = (1) k (R,c)-WNNs found; (2) k + gamma*n candidates
                       checked at some radius
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from .datagen import make_query_set  # noqa: F401  (re-export convenience)
from .distances import weighted_lp_np
from .families import LpFamilyParams, hash_codes_np, sample_lp_family
from .params import PlanConfig
from .partition import GroupPlan, PartitionResult, partition
from .serving_plan import GroupServingPlan, ServingPlan

__all__ = ["WLSHIndex", "SearchResult", "SearchStats", "BLOCK_BYTES"]

BLOCK_BYTES = 4096  # paper Sec. 5.1.3
_ENTRY_BYTES = 8  # (point id, code) per hash-table entry
_COORD_BYTES = 4


@dataclasses.dataclass
class SearchStats:
    stop_level: int
    n_checked: int  # candidates whose exact distance was computed
    n_collisions: int  # hash-table entries scanned (identify cost)
    io_blocks: float  # paper-style I/O: identify + check, in 4KB blocks
    found_k: bool


@dataclasses.dataclass
class SearchResult:
    ids: np.ndarray  # (k,) indices into the data set (-1 = missing)
    dists: np.ndarray  # (k,) distances under the query weight
    stats: SearchStats


@dataclasses.dataclass
class BuiltGroup:
    plan: GroupPlan
    fam: LpFamilyParams
    codes: np.ndarray  # (n, beta) int32 raw codes (dense path / export)
    # (sorted_codes, sorted_ids), each (beta, n) int32: per-table ascending
    # codes and their point ids, built on the group's first host search
    # (``sorted_tables``); None until then
    tables: tuple[np.ndarray, np.ndarray] | None = None

    def sorted_tables(self) -> tuple[np.ndarray, np.ndarray]:
        """The group's sorted hash tables, built once and kept."""
        if self.tables is None:
            order = np.argsort(self.codes, axis=0, kind="stable")  # (n, beta)
            sorted_codes = np.take_along_axis(self.codes, order, axis=0)
            self.tables = (sorted_codes.T.copy(),
                           order.T.astype(np.int32).copy())
        return self.tables


def _bucket_slices(sorted_codes: np.ndarray, q_codes: np.ndarray,
                   width: int) -> tuple[np.ndarray, np.ndarray]:
    """Per table, the slice [lo, hi) of its ascending codes that holds the
    query's bucket of this width: codes in [b_lo, b_lo + width) with
    ``b_lo = (q_code // width) * width``.

    The bounds are searched inclusively and clipped to the codes' dtype,
    so a wide bucket never casts (and copies) the table.
    """
    info = np.iinfo(sorted_codes.dtype)
    b_lo = (q_codes.astype(np.int64) // width) * width
    first = np.maximum(b_lo, info.min).astype(sorted_codes.dtype)
    last = np.minimum(b_lo + (width - 1), info.max).astype(sorted_codes.dtype)
    lo = np.array([np.searchsorted(t, v, side="left")
                   for t, v in zip(sorted_codes, first)], dtype=np.int64)
    hi = np.array([np.searchsorted(t, v, side="right")
                   for t, v in zip(sorted_codes, last)], dtype=np.int64)
    return lo, hi


def _level_collisions(sorted_codes: np.ndarray, sorted_ids: np.ndarray,
                      q_codes: np.ndarray, c: int, n_levels: int):
    """Yield, for levels j = 0..n_levels, the ids that newly collide with
    the query at level j: one entry per (table, point) whose code enters
    the query's level-j bucket, ``code // c**j == q_code // c**j``.

    Level buckets nest (``(x // c**j) // c == x // c**(j+1)``), so each
    table's slice only widens and the new entries are the two rings
    around the previous level's slice: summed over levels, every point's
    count is the number of tables it collides with at that level.
    """
    prev_lo = prev_hi = None
    for j in range(n_levels + 1):
        lo, hi = _bucket_slices(sorted_codes, q_codes, c**j)
        if prev_lo is None:
            prev_lo = prev_hi = lo
        parts = []
        for t in range(len(lo)):
            parts.append(sorted_ids[t, lo[t]:prev_lo[t]])
            parts.append(sorted_ids[t, prev_hi[t]:hi[t]])
        yield np.concatenate(parts) if parts else sorted_ids[:0, 0]
        prev_lo, prev_hi = lo, hi


class WLSHIndex:
    """Multi-weight (c, k)-WNN index over one data set.

    Parameters follow the paper: ``tau`` caps per-group tables, ``v/v_prime``
    enable bound relaxation (1/1 = strict Theorem 1), ``use_reduction``
    applies collision-threshold reduction at query time.
    """

    def __init__(
        self,
        data: np.ndarray,
        weights: np.ndarray,
        cfg: PlanConfig,
        tau: float,
        value_range: float = 10_000.0,
        v: int = 1,
        v_prime: int = 1,
        use_reduction: bool = True,
        seed: int = 0,
        materialize: bool = False,
    ):
        if abs(cfg.c - round(cfg.c)) > 1e-9 or cfg.c < 2:
            raise ValueError("virtual rehashing requires integer c >= 2")
        self.data = np.asarray(data, dtype=np.float32)
        self.weights = np.asarray(weights, dtype=np.float64)
        self.cfg = dataclasses.replace(cfg, n=len(self.data))
        self.tau = tau
        self.value_range = value_range
        self.v, self.v_prime = v, v_prime
        self.use_reduction = use_reduction
        self.seed = seed
        self.part: PartitionResult = partition(
            self.weights, self.cfg, value_range, tau, v=v, v_prime=v_prime
        )
        self._built: dict[int, BuiltGroup] = {}
        if materialize:
            for gi in range(len(self.part.groups)):
                self._group(gi).sorted_tables()

    # ------------------------------------------------------------------ build

    @property
    def beta_total(self) -> int:
        return self.part.beta_total

    @property
    def n(self) -> int:
        return len(self.data)

    def _group(self, gi: int) -> BuiltGroup:
        if gi in self._built:
            return self._built[gi]
        plan = self.part.groups[gi]
        fam = sample_lp_family(
            d=self.data.shape[1],
            beta=plan.beta_group,
            p=self.cfg.p,
            width=plan.width,
            center_weight=self.weights[plan.center_id],
            ratio_cap=plan.ratio_cap,
            c=self.cfg.c,
            seed=self.seed + 7919 * gi,
        )
        built = BuiltGroup(plan=plan, fam=fam,
                           codes=hash_codes_np(self.data, fam))  # (n, beta)
        self._built[gi] = built
        return built

    # ----------------------------------------------------------------- export

    def _effective_mus(self, plan: GroupPlan) -> np.ndarray:
        """Per-member integer collision thresholds (reduction applied)."""
        mus = plan.mus_reduced if self.use_reduction else plan.mus
        return np.maximum(1, np.ceil(mus - 1e-9)).astype(np.int32)

    def export_serving_plan(self, include_codes: bool = True) -> ServingPlan:
        """Flat, serializable description of every table group.

        This is the only core -> device handoff: the sharded engine and the
        retrieval service consume the plan, never `WLSHIndex` internals.
        ``include_codes`` ships the host-computed bucket codes so a device
        engine reproduces the host oracle's candidate sets exactly.
        """
        groups = []
        for gi in range(len(self.part.groups)):
            built = self._group(gi)
            plan = built.plan
            groups.append(
                GroupServingPlan(
                    group_id=gi,
                    center_id=int(plan.center_id),
                    beta_group=int(plan.beta_group),
                    width=float(built.fam.width),
                    levels_cap=int(built.fam.levels_cap),
                    member_ids=plan.member_ids.astype(np.int64),
                    beta_members=plan.betas.astype(np.int32),
                    mu_members=self._effective_mus(plan),
                    r_min_members=plan.r_min_members.astype(np.float64),
                    n_levels_members=plan.n_levels.astype(np.int32),
                    proj=built.fam.proj,
                    b_int=built.fam.b_int,
                    b_frac=built.fam.b_frac,
                    center_weight=built.fam.center_weight,
                    p=float(self.cfg.p),
                    codes=built.codes if include_codes else None,
                )
            )
        return ServingPlan(
            n=self.n,
            d=self.data.shape[1],
            p=float(self.cfg.p),
            c=int(round(self.cfg.c)),
            gamma_n=float(self.cfg.gamma_n),
            tau=float(self.part.tau),
            weights=self.weights.copy(),
            group_of=self.part.group_of.copy(),
            member_slot=self.part.member_slot.copy(),
            groups=tuple(groups),
            corpus_epoch=self.n,
        )

    # ----------------------------------------------------------------- search

    def _member_params(self, weight_id: int):
        gi = int(self.part.group_of[weight_id])
        built = self._group(gi)
        slot = int(self.part.member_slot[weight_id])
        plan = built.plan
        beta_i = int(plan.betas[slot])
        mu_i = int(self._effective_mus(plan)[slot])
        return built, slot, beta_i, mu_i

    @staticmethod
    def _c_eff(cfg_c: float, c: float | None) -> int:
        """Resolve an optional approximation-ratio override to int >= 2.

        Query-time ``c`` relaxation is the degradation ladder's oracle
        knob: the hash tables are c-independent (virtual rehashing only
        regroups buckets as ``code // c**j``), so a built index can be
        queried at any integer ratio >= the configured one without
        rebuilding — exactly what the serving ladder does via
        pre-compiled relaxed steps.
        """
        c_eff = cfg_c if c is None else c
        if c_eff != int(round(c_eff)) or int(round(c_eff)) < 2:
            raise ValueError(
                f"approximation ratio c must be an integer >= 2, got {c_eff}"
            )
        return int(round(c_eff))

    def search(
        self, q: np.ndarray, weight_id: int, k: int = 1,
        c: float | None = None,
    ) -> SearchResult:
        """(c, k)-WNN search under weight vector ``weight_id`` (Algorithm 2).

        Faithful C2LSH level loop with incremental collision counting over
        the group's first beta_{W_i} tables.  ``c`` optionally overrides
        the configured approximation ratio at query time (see ``_c_eff``).
        """
        built, slot, beta_i, mu_i = self._member_params(weight_id)
        plan = built.plan
        w_i = self.weights[weight_id]
        r_min = float(plan.r_min_members[slot])
        n_levels = int(plan.n_levels[slot])
        c = self._c_eff(self.cfg.c, c)
        n = self.n
        budget = k + int(math.ceil(self.cfg.gamma_n))  # == gamma * n, float-exact

        q = np.asarray(q, dtype=np.float32)
        q_codes = hash_codes_np(q[None, :], built.fam)[0][:beta_i]
        sc, sids = (t[:beta_i] for t in built.sorted_tables())

        counts = np.zeros(n, dtype=np.int32)
        checked = np.zeros(n, dtype=bool)
        cand_ids: list[np.ndarray] = []
        cand_dists: list[np.ndarray] = []
        n_collisions = 0
        n_checked = 0
        n_good = 0
        stop_level = n_levels
        found_k = False

        for j, inc in enumerate(
                _level_collisions(sc, sids, q_codes, c, n_levels)):
            if inc.size:
                n_collisions += inc.size
                np.add.at(counts, inc, 1)
            # identify frequent, not-yet-checked candidates
            freq = np.where((counts >= mu_i) & ~checked)[0]
            if freq.size:
                take = freq[: max(0, budget - n_checked)]
                if take.size:
                    d = weighted_lp_np(self.data[take], q, w_i, self.cfg.p)
                    checked[take] = True
                    n_checked += take.size
                    cand_ids.append(take)
                    cand_dists.append(d)
            R = r_min * (c**j)
            if cand_dists:
                all_d = np.concatenate(cand_dists)
                n_good = int(np.sum(all_d <= c * R))
            if n_good >= k or n_checked >= budget:
                stop_level = j
                found_k = n_good >= k
                break

        if cand_ids:
            ids = np.concatenate(cand_ids)
            dists = np.concatenate(cand_dists)
            top = np.argsort(dists, kind="stable")[:k]
            out_ids = np.full(k, -1, dtype=np.int64)
            out_d = np.full(k, np.inf)
            out_ids[: top.size] = ids[top]
            out_d[: top.size] = dists[top]
        else:
            out_ids = np.full(k, -1, dtype=np.int64)
            out_d = np.full(k, np.inf)

        blocks_identify = n_collisions / (BLOCK_BYTES / _ENTRY_BYTES)
        blocks_check = n_checked * max(
            1, math.ceil(self.data.shape[1] * _COORD_BYTES / BLOCK_BYTES)
        )
        stats = SearchStats(
            stop_level=stop_level,
            n_checked=n_checked,
            n_collisions=n_collisions,
            io_blocks=blocks_identify + blocks_check,
            found_k=found_k,
        )
        return SearchResult(ids=out_ids, dists=out_d, stats=stats)

    # ------------------------------------------------------------ dense oracle

    def search_dense(
        self, q: np.ndarray, weight_id: int, k: int = 1,
        c: float | None = None,
    ) -> SearchResult:
        """Dense search semantics (the TPU formulation), numpy oracle.

        A point's L_freq is the first level j at which at least mu of the
        member's first beta_{W_i} tables collide (``code // c**j`` equal):
        the mu-th order statistic of its per-table first-collision levels.
        The paper's stop conditions then apply level by level to *every*
        point with L_freq <= j (``search`` instead checks candidates in
        discovery order up to the budget).  Must agree with ``search`` on
        the candidate *sets*; used to validate kernels and the sharded
        engine.  ``c`` optionally overrides the configured approximation
        ratio (see ``_c_eff``).

        The per-point collision counts grow level by level through the
        same bucket walk as ``search`` (``_level_collisions``) and work
        stops at the stop level, so the cost is the buckets probed, not
        n x beta per level; ``tests/test_wlsh.py`` pins this to the dense
        per-(point, table) formula field for field.
        """
        built, slot, beta_i, mu_i = self._member_params(weight_id)
        plan = built.plan
        w_i = self.weights[weight_id]
        r_min = float(plan.r_min_members[slot])
        n_levels = int(plan.n_levels[slot])
        c = self._c_eff(self.cfg.c, c)
        n = self.n
        budget = k + int(math.ceil(self.cfg.gamma_n))  # == gamma * n, float-exact

        q = np.asarray(q, dtype=np.float32)
        q_codes = hash_codes_np(q[None, :], built.fam)[0][:beta_i]
        counts = np.zeros(n, dtype=np.int64)  # tables colliding at level j
        dists = np.empty(n)  # filled as points become frequent
        freq = np.zeros(n, dtype=bool)
        n_collisions = 0
        stop_level, n_checked, found_k = n_levels, 0, False
        sc, sids = (t[:beta_i] for t in built.sorted_tables())
        for j, inc in enumerate(
                _level_collisions(sc, sids, q_codes, c, n_levels)):
            n_collisions += inc.size
            counts += np.bincount(inc, minlength=n)
            new = np.where((counts >= mu_i) & ~freq)[0]
            if new.size:
                dists[new] = weighted_lp_np(self.data[new], q, w_i,
                                            self.cfg.p)
                freq[new] = True
            idx = np.where(freq)[0]
            n_chk = min(idx.size, budget)
            R = r_min * (c**j)
            n_good = int(np.sum(dists[idx] <= c * R))
            if n_good >= k or n_chk >= budget:
                stop_level, n_checked, found_k = j, n_chk, n_good >= k
                break
            n_checked = n_chk
        top = idx[np.argsort(dists[idx], kind="stable")[:k]]
        out_ids = np.full(k, -1, dtype=np.int64)
        out_d = np.full(k, np.inf)
        out_ids[: top.size] = top
        out_d[: top.size] = dists[top]
        stats = SearchStats(
            stop_level=stop_level,
            n_checked=n_checked,
            n_collisions=n_collisions,
            io_blocks=float("nan"),
            found_k=found_k,
        )
        return SearchResult(ids=out_ids, dists=out_d, stats=stats)
