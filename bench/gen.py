"""Inputs made from the seed: corpus, weight set and the traffic schedule.

The corpus and weight-set generators are the paper's (Sec. 5.1.1, Tables 3
and 5), kept here so that a change to the program cannot change the
yardstick: integer points uniform in [0, value_range]^d, and weight sets
as the union of ``n_subset`` subsets that each draw every coordinate from
one of ``n_subrange`` equal subranges of [1, 10].

The traffic schedule gives every seed the same work in another order:
the gaps between due times are a fixed set of exponential quantiles (an
open-loop Poisson stream at the mix's rate, stretched to fill the window
exactly), and the weight ids are a fixed multiset with Zipf shares,
ranked by weight id so that user 0 is the hottest.  Only the order of
both, the corpus rows the queries start from and their noise come from
the seed.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Schedule", "make_dataset", "make_schedule", "make_weight_set",
           "seeds", "zipf_counts"]


def seeds(seed: int, n: int = 4) -> list[np.random.SeedSequence]:
    """``n`` independent child seed sequences of a run's ``--seed``.

    Children, in order: corpus, hash functions, traffic, check sample.
    Any whole number works; negative ones wrap into [0, 2**64).
    """
    return np.random.SeedSequence(int(seed) % 2**64).spawn(n)


def make_dataset(n: int, d: int, value_range: float,
                 seq: np.random.SeedSequence) -> np.ndarray:
    """(n, d) float32 integer points uniform in [0, value_range]^d."""
    rng = np.random.default_rng(seq)
    return rng.integers(0, int(value_range) + 1, size=(n, d)).astype(
        np.float32)


def make_weight_set(size: int, d: int, n_subset: int, n_subrange: int,
                    seed: int, lo: float = 1.0,
                    hi: float = 10.0) -> np.ndarray:
    """(size, d) float64 weight vectors, the paper's Table 5 generator."""
    if size % n_subset:
        raise ValueError(f"|S|={size} is not a multiple of "
                         f"n_subset={n_subset}")
    per = size // n_subset
    rng = np.random.default_rng(seed)
    edges = np.linspace(lo, hi, n_subrange + 1)
    out = np.empty((size, d), dtype=np.float64)
    for s in range(n_subset):
        sub = rng.integers(0, n_subrange, size=d)
        out[s * per:(s + 1) * per] = rng.uniform(edges[sub], edges[sub + 1],
                                                 size=(per, d))
    return out


def zipf_counts(n: int, n_items: int, s: float) -> np.ndarray:
    """Counts summing to ``n`` with Zipf(s) shares over ranks 0..n_items-1.

    Largest-remainder rounding of ``n * p_i``, ``p_i ~ 1 / (i + 1)**s``.
    """
    p = 1.0 / np.arange(1, n_items + 1, dtype=np.float64) ** s
    p /= p.sum()
    exact = n * p
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Open-loop requests of one window, in due order."""

    due_s: np.ndarray  # (N,) float64 seconds after the window opens
    weight_ids: np.ndarray  # (N,) int64
    queries: np.ndarray  # (N, d) float32

    def __len__(self) -> int:
        return len(self.due_s)


def make_schedule(traffic: dict, seconds: float, data: np.ndarray,
                  n_weights: int, seq: np.random.SeedSequence) -> Schedule:
    """The window's requests for a traffic mix (see the module docstring).

    ``traffic`` keys: ``rate_qps`` (offered load), ``zipf_s`` (weight-id
    popularity exponent) and ``q_noise`` (std of the Gaussian noise added
    to a uniformly drawn corpus row to make each query).
    """
    rate = float(traffic["rate_qps"])
    n_req = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seq)
    u = (np.arange(n_req) + 0.5) / n_req
    gaps = rng.permutation(-np.log1p(-u))
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    counts = zipf_counts(n_req, n_weights, float(traffic["zipf_s"]))
    wids = rng.permutation(np.repeat(np.arange(n_weights), counts))
    rows = rng.integers(0, len(data), size=n_req)
    noise = rng.normal(0.0, float(traffic["q_noise"]),
                       size=(n_req, data.shape[1]))
    queries = (data[rows] + noise).astype(np.float32)
    return Schedule(due_s=due, weight_ids=wids.astype(np.int64),
                    queries=queries)
