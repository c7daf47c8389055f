"""Plain reference for the served answers, the control, and the comparison.

The reference restates the dense (c, k)-WNN semantics of the paper's
Algorithm 2 from scratch; it imports nothing of the program.  From the
program it takes only the index's definition as the planner exported it,
``IndexDefinition``: each group's sampled hash functions and each weight
vector's table count, collision threshold, radius base and level cap.  It
recomputes everything the served path derives from them:

1. every row's bucket codes, in float64 on the host (Eq. 7 with the
   exact integer/fraction split of the offset);
2. each row's first frequent level for the query: the first level j at
   which at least mu of the member's first beta tables put the row in the
   query's bucket ``code // c**j`` (integer compares against the
   bucket's code range, on the device, one query at a time);
3. the stop level: the first j at which k rows with first frequent level
   <= j lie within c * r_min * c**j, or the budget k + ceil(gamma_n) of
   such rows is reached, else the member's level cap;
4. the answer: the k nearest rows, by float64 weighted l_p distance, among
   rows with first frequent level <= the stop level; ``n_checked`` is
   their count capped at the budget.

Distances are float64 wherever they decide something: a float32 pass on
the device (with a proven error bound) settles every comparison that
lies clearly on one side, and float64 on the host settles the rest and
orders the answer.

The control is the same reference with its distances computed one step
below the precision the served path states (see ``control_distances``).
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Answers", "IndexDefinition", "compare", "control_distances",
           "reference_answers"]


@dataclasses.dataclass(frozen=True)
class IndexDefinition:
    """What the reference takes from the planner: functions + thresholds."""

    p: float
    c: int
    budget: int  # k + ceil(gamma_n)
    families: tuple[dict, ...]  # per group: proj, b_int, b_frac, width,
    # center_weight
    member: dict[int, tuple[int, int, int, float, int]]  # weight id ->
    # (group, beta, mu, r_min, n_levels)


@dataclasses.dataclass
class Answers:
    """Answers to a list of queries, one row each."""

    ids: np.ndarray  # (Q, k) int64, -1 = missing
    dists: np.ndarray  # (Q, k) float64, inf = missing
    stop: np.ndarray  # (Q,) int64
    n_checked: np.ndarray  # (Q,) int64


def group_codes(points: np.ndarray, fam: dict,
                block: int = 8_192) -> np.ndarray:
    """(n, beta) int32 level-0 bucket codes, float64, in row blocks.

    Blocks run on a thread per core: numpy's matrix product releases the
    interpreter lock.
    """
    a = np.asarray(fam["proj"], np.float64)
    w = np.asarray(fam["center_weight"], np.float64)
    b_frac = np.asarray(fam["b_frac"], np.float64)
    b_int = np.asarray(fam["b_int"], np.int64)
    out = np.empty((len(points), a.shape[1]), np.int32)

    def rows(lo: int) -> None:
        x = np.asarray(points[lo:lo + block], np.float64) * w
        u = x @ a / float(fam["width"]) + b_frac
        out[lo:lo + block] = np.floor(u).astype(np.int64) + b_int

    with concurrent.futures.ThreadPoolExecutor(os.cpu_count()) as pool:
        list(pool.map(rows, range(0, len(points), block)))
    return out


_I32 = np.iinfo(np.int32)


def _level_buckets(q_codes: np.ndarray, c: int, n_levels: int):
    """(n_levels + 1, beta) inclusive code ranges of the query's buckets.

    Level j's bucket of a table holds the codes x with
    ``x // c**j == q // c**j``: the range [lo, lo + c**j - 1] with
    ``lo = (q // c**j) * c**j``, clipped to the codes' int32 range.
    """
    width = c ** np.arange(n_levels + 1, dtype=np.int64)[:, None]
    lo = (q_codes.astype(np.int64)[None, :] // width) * width
    hi = lo + width - 1
    return (np.clip(lo, _I32.min, _I32.max).astype(np.int32),
            np.clip(hi, _I32.min, _I32.max).astype(np.int32))


@jax.jit
def _first_frequent_level(codes, lo, hi, beta, mu):
    """(n,) first level at which >= mu of the first beta tables collide.

    ``lo``/``hi`` are the query's bucket ranges per level
    (``_level_buckets``); rows that never become frequent read
    ``n_levels + 1``.
    """
    lane_ok = jnp.arange(codes.shape[1]) < beta
    never = lo.shape[0]

    def level(j, lf):
        inside = (codes >= lo[j][None, :]) & (codes <= hi[j][None, :])
        hits = jnp.sum(inside & lane_ok[None, :], axis=1)
        return jnp.where((hits >= mu) & (lf == never), j, lf)

    lf0 = jnp.full((codes.shape[0],), never, jnp.int32)
    return jax.lax.fori_loop(0, lo.shape[0], level, lf0)


def _weighted_lp(diff, p: float, xp=np):
    """Weighted l_p norms of the rows of ``diff`` = |x - q| * w."""
    if p == 2.0:
        return xp.sqrt(xp.sum(diff * diff, axis=-1))
    if p == 1.0:
        return xp.sum(diff, axis=-1)
    return xp.sum(diff**p, axis=-1) ** (1.0 / p)


@functools.partial(jax.jit, static_argnames=("p",))
def _distances_f32(points, q, w, *, p: float):
    """(n,) float32 weighted distances from coordinate differences.

    Every term's relative error is a few float32 roundings and a sum of d
    positive terms adds at most d of them, so these lie within
    ``d * 2**-22`` (under 2.3e-4 at d = 960) of the float64 distance:
    the reference trusts them only outside a margin of ``_MARGIN``.
    """
    return _weighted_lp(jnp.abs((points - q[None, :]) * w[None, :]), p,
                        xp=jnp)


_MARGIN = 1e-3


def _split_bf16(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot_high(u, x):
    """(n, d) @ (d,) as the ``high`` (bf16 x 3) contraction computes it."""
    uh, ul = _split_bf16(u)
    xh, xl = _split_bf16(x)

    def dot(a, b):
        return jnp.dot(b, a, preferred_element_type=jnp.float32)

    return dot(uh, xh) + dot(ul, xh) + dot(uh, xl)


@functools.partial(jax.jit, static_argnames=("p",))
def control_distances(points, q, w, *, p: float):
    """(n,) weighted distances one precision step below the served path.

    The served path states float32 distances, with its p = 2 contractions
    at ``highest``.  One step below: p = 2 runs the same norms-and-cross
    expansion with both contractions at ``high`` (three bf16 passes,
    emulated so that every backend rounds alike); any other p runs the
    elementwise difference in bfloat16.
    """
    q = q.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if p == 2.0:
        w2 = w * w
        d2 = (jnp.sum(w2 * q * q) - 2.0 * _dot_high(w2 * q, points)
              + _dot_high(w2, points * points))
        return jnp.sqrt(jnp.maximum(d2, 0.0))
    bf = jnp.bfloat16
    diff = jnp.abs((q.astype(bf)[None, :] - points.astype(bf))
                   * w.astype(bf)[None, :]).astype(jnp.float32)
    if p == 1.0:
        return jnp.sum(diff, axis=1)
    return jnp.sum(diff**p, axis=1) ** (1.0 / p)


def _decide(lf: np.ndarray, approx: np.ndarray, exact_of, *, k: int,
            n_levels: int, c: int, r_min: float, budget: int):
    """Stop level, k nearest frequent rows and n_checked (step 3 and 4).

    ``approx`` holds every row's distance to within ``_MARGIN`` (relative)
    of its exact one; ``exact_of(idx)`` gives exact distances, and is
    asked only where ``approx`` cannot decide a comparison.  With
    ``exact_of`` None, ``approx`` is taken as exact (the control).
    """
    m = 0.0 if exact_of is None else _MARGIN
    exact = {}

    def dist(idx: np.ndarray) -> np.ndarray:
        if exact_of is None:
            return approx[idx]
        todo = np.array([i for i in idx if i not in exact], np.int64)
        if len(todo):
            exact.update(zip(todo.tolist(), exact_of(todo)))
        return np.array([exact[i] for i in idx])

    n_freq = np.cumsum(np.bincount(lf, minlength=n_levels + 2))
    seen: list[np.ndarray] = []
    stop = n_levels
    for j in range(n_levels + 1):
        seen.append(np.flatnonzero(lf == j))
        cand = np.concatenate(seen)
        thr = c * r_min * c**j
        a = approx[cand]
        near = cand[(a > thr * (1 - m)) & (a <= thr * (1 + m))]
        n_good = int(np.sum(a <= thr * (1 - m)))
        n_good += int(np.sum(dist(near) <= thr)) if len(near) else 0
        if n_good >= k or n_freq[j] >= budget:
            stop = j
            break
    cand = np.flatnonzero(lf <= stop)
    if len(cand) > k:
        kth = np.partition(approx[cand], k - 1)[k - 1]
        cand = cand[approx[cand] <= kth * (1 + 3 * m)]
    d = dist(cand)
    top = np.lexsort((cand, d))[:k]
    ids = np.full(k, -1, np.int64)
    dists = np.full(k, np.inf)
    ids[:len(top)] = cand[top]
    dists[:len(top)] = d[top]
    return ids, dists, stop, min(int(n_freq[stop]), budget)


def _exact(data, q64, w, p, idx):
    """Float64 weighted distances of rows ``idx``."""
    return _weighted_lp(np.abs((np.asarray(data[idx], np.float64) - q64)
                               * w), p)


def reference_answers(data: np.ndarray, weights: np.ndarray,
                      queries: np.ndarray, weight_ids: np.ndarray,
                      defn: IndexDefinition, k: int, control: bool = False,
                      check_ids=()) -> tuple[Answers, list[np.ndarray]]:
    """Answers of the plain reference (or, with ``control``, the control).

    Also returns, for each (Q, k) array of ``check_ids`` (another
    answerer's ids), the float64 distance of every id that is a candidate
    at the reference's stop level, inf for any other; ``compare`` reads
    them to tell a swap of tied rows from a wrong answer.  Works group by
    group so that one group's codes are on the device at a time; the
    caller frees the program's state first.
    """
    nq = len(queries)
    out = Answers(ids=np.full((nq, k), -1, np.int64),
                  dists=np.full((nq, k), np.inf),
                  stop=np.zeros(nq, np.int64),
                  n_checked=np.zeros(nq, np.int64))
    checked = [np.full((nq, k), np.inf) for _ in check_ids]
    groups = np.array([defn.member[int(w)][0] for w in weight_ids])
    points_dev = jnp.asarray(data)
    t_codes = 0.0
    t0 = time.perf_counter()
    for g in np.unique(groups):
        fam = defn.families[int(g)]
        t1 = time.perf_counter()
        codes = jnp.asarray(group_codes(data, fam))
        t_codes += time.perf_counter() - t1
        for i in np.flatnonzero(groups == g):
            _, beta, mu, r_min, n_levels = defn.member[int(weight_ids[i])]
            q = np.asarray(queries[i], np.float32)
            w = np.asarray(weights[int(weight_ids[i])], np.float64)
            lo, hi = _level_buckets(group_codes(q[None, :], fam)[0], defn.c,
                                    n_levels)
            lf = np.asarray(_first_frequent_level(codes, lo, hi, beta, mu))
            dist_fn = control_distances if control else _distances_f32
            approx = np.asarray(dist_fn(points_dev, jnp.asarray(q),
                                        jnp.asarray(w, jnp.float32),
                                        p=defn.p), np.float64)
            exact_of = None if control else functools.partial(
                _exact, data, q.astype(np.float64), w, defn.p)
            (out.ids[i], out.dists[i], out.stop[i],
             out.n_checked[i]) = _decide(
                lf, approx, exact_of, k=k, n_levels=n_levels, c=defn.c,
                r_min=r_min, budget=defn.budget)
            for ids, got in zip(check_ids, checked):
                ok = (ids[i] >= 0) & (ids[i] < len(data))
                ok[ok] = lf[ids[i][ok]] <= out.stop[i]
                got[i, ok] = _exact(data, q.astype(np.float64), w, defn.p,
                                    ids[i][ok])
        del codes
    print(f"{'control' if control else 'reference'}: {nq} answers in "
          f"{time.perf_counter() - t0:.1f} s, {t_codes:.1f} s of it for "
          f"the codes", file=sys.stderr)
    return out, checked


def compare(served: Answers, ref: Answers, served_ref_dists: np.ndarray,
            tie_rel: float) -> dict[str, float]:
    """The numbers that decide ``correct``, served against reference.

    ``mismatch_share``: share of answers whose stop level or n_checked
    differ, or whose ids differ at some rank.  Ids that differ still match
    where they swap rows tied to within float32 rounding: the served id is
    a candidate whose float64 distance (``served_ref_dists``, from
    ``reference_answers``) lies within ``tie_rel`` of the reference's at
    that rank, and no id repeats.  ``dist_rel_err_max``: the widest
    relative gap between a served distance and the reference's at the
    same rank, over ranks where both hold a row (a missing row is a
    mismatch).
    """
    with np.errstate(invalid="ignore", divide="ignore"):
        tied = (np.isfinite(served_ref_dists) & np.isfinite(ref.dists)
                & (np.abs(served_ref_dists - ref.dists)
                   <= tie_rel * np.abs(ref.dists)))
        gap = np.abs(served.dists - ref.dists) / np.maximum(ref.dists,
                                                            1e-30)
    rank_ok = (served.ids == ref.ids) | tied
    ids = np.where(served.ids >= 0, served.ids, -1 - np.arange(
        served.ids.shape[1])[None, :])
    distinct = np.array([len(set(r.tolist())) == len(r) for r in ids],
                        dtype=bool)
    same = (np.all(rank_ok, axis=1) & distinct
            & (served.stop == ref.stop)
            & (served.n_checked == ref.n_checked))
    both = np.isfinite(served.dists) & np.isfinite(ref.dists)
    rel = float(np.max(gap[both])) if both.any() else 0.0
    return {"mismatch_share": float(1.0 - same.mean()) if len(same) else 0.0,
            "dist_rel_err_max": rel if math.isfinite(rel) else 1e300}
