"""End-to-end WLSH index behaviour: accuracy guarantees, faithful vs dense
path agreement, C2LSH degeneration, I/O accounting."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.core.c2lsh import C2LSH
from repro.core.datagen import make_dataset, make_query_set, make_weight_set
from repro.core.distances import weighted_lp_np
from repro.core.families import hash_codes_np
from repro.core.params import PlanConfig
from repro.core.wlsh import WLSHIndex


def _overall_ratio(idx, qs, k, use_dense=False):
    """Average overall ratio (paper Eq. 16) over a query set."""
    ratios = []
    for q in qs.points:
        for wid in qs.weight_ids:
            fn = idx.search_dense if use_dense else idx.search
            res = fn(q, weight_id=int(wid), k=k)
            got = res.ids[res.ids >= 0]
            if got.size == 0:
                ratios.append(np.inf)
                continue
            w = idx.weights[int(wid)]
            exact = np.sort(weighted_lp_np(idx.data, q, w, idx.cfg.p))[: got.size]
            mine = np.sort(
                weighted_lp_np(idx.data[got], q, w, idx.cfg.p)
            )
            ratios.append(float(np.mean(mine / np.maximum(exact, 1e-12))))
    return float(np.mean(ratios))


@pytest.fixture(scope="module", params=[1.0, 2.0], ids=["l1", "l2"])
def built(request):
    p = request.param
    data = make_dataset(n=3_000, d=24, seed=11)
    weights = make_weight_set(size=10, d=24, n_subset=2, n_subrange=10, seed=12)
    cfg = PlanConfig(p=p, c=3, n=len(data), gamma_n=100.0)
    idx = WLSHIndex(
        data, weights, cfg, tau=1_000.0 if p == 1.0 else 500.0,
        v=6, v_prime=6, seed=3,
    )
    qs = make_query_set(data, weights, n_query_points=8, n_query_weights=3,
                        seed=13)
    return idx, qs


def test_accuracy_guarantee(built):
    """Average overall ratio must be well under the approximation ratio c."""
    idx, qs = built
    ratio = _overall_ratio(idx, qs, k=5)
    assert ratio < idx.cfg.c, f"avg overall ratio {ratio} >= c={idx.cfg.c}"


def test_dense_path_matches_guarantee(built):
    idx, qs = built
    ratio = _overall_ratio(idx, qs, k=5, use_dense=True)
    assert ratio < idx.cfg.c


def test_faithful_vs_dense_same_stop_semantics(built):
    """Both paths implement identical stop conditions -> same stop level and
    the same frequent-candidate *sets* (order may differ)."""
    idx, qs = built
    for q in qs.points[:4]:
        for wid in qs.weight_ids[:2]:
            r1 = idx.search(q, weight_id=int(wid), k=3)
            r2 = idx.search_dense(q, weight_id=int(wid), k=3)
            assert r1.stats.stop_level == r2.stats.stop_level
            # top-1 distances agree (best candidate is identical)
            if r1.ids[0] >= 0 and r2.ids[0] >= 0:
                np.testing.assert_allclose(
                    r1.dists[0], r2.dists[0], rtol=1e-6
                )


def _dense_formula(idx, q, weight_id, k, c=None):
    """search_dense's semantics written out densely: per (point, table)
    first-collision level, the mu-th order statistic, then the stop
    conditions over every point — (ids, dists, stop, n_checked,
    n_collisions, found_k)."""
    built, slot, beta_i, mu_i = idx._member_params(weight_id)
    r_min = float(built.plan.r_min_members[slot])
    n_levels = int(built.plan.n_levels[slot])
    c = idx._c_eff(idx.cfg.c, c)
    budget = k + int(np.ceil(idx.cfg.gamma_n))
    q = np.asarray(q, np.float32)
    b = hash_codes_np(q[None, :], built.fam)[0][:beta_i].astype(np.int64)
    a = built.codes[:, :beta_i].astype(np.int64)
    jmin = np.full(a.shape, n_levels + 1, np.int16)
    for j in range(n_levels + 1):
        jmin[(a == b[None, :]) & (jmin > n_levels)] = j
        a //= c
        b //= c
    if mu_i > beta_i:
        l_freq = np.full(idx.n, n_levels + 1, np.int16)
    else:
        l_freq = np.partition(jmin, mu_i - 1, axis=1)[:, mu_i - 1]
    dists = weighted_lp_np(idx.data, q, idx.weights[weight_id], idx.cfg.p)
    stop, n_checked, found_k = n_levels, 0, False
    for j in range(n_levels + 1):
        freq = l_freq <= j
        n_chk = min(int(freq.sum()), budget)
        n_good = int(np.sum(freq & (dists <= c * r_min * c**j)))
        if n_good >= k or n_chk >= budget:
            stop, n_checked, found_k = j, n_chk, n_good >= k
            break
        n_checked = n_chk
    cand = np.where(l_freq <= stop)[0]
    top = cand[np.argsort(dists[cand], kind="stable")[:k]]
    ids = np.full(k, -1, np.int64)
    out_d = np.full(k, np.inf)
    ids[: top.size] = top
    out_d[: top.size] = dists[top]
    return ids, out_d, stop, n_checked, int(np.sum(jmin <= stop)), found_k


@pytest.mark.parametrize("p,tau", [(2.0, 500.0), (1.0, 1_000.0),
                                   (0.5, 2_000.0)])
@pytest.mark.parametrize("c", [None, 5])
def test_search_dense_matches_dense_formula(p, tau, c):
    """The collision-driven search_dense equals the dense formula it
    implements, field for field, including the query-time c override."""
    data = make_dataset(n=3_000, d=16, seed=21)
    weights = make_weight_set(size=6, d=16, n_subset=2, n_subrange=10,
                              seed=22)
    idx = WLSHIndex(data, weights, PlanConfig(p=p, c=3, n=len(data),
                                              gamma_n=100.0),
                    tau=tau, v=4, v_prime=4, seed=23)
    rng = np.random.default_rng(24)
    for qi in range(32):
        q = data[rng.integers(len(data))] + rng.normal(0, 30.0, 16)
        wid = int(rng.integers(len(weights)))
        k = (1, 5, 10)[qi % 3]
        got = idx.search_dense(q, weight_id=wid, k=k, c=c)
        ids, dists, stop, n_checked, n_coll, found_k = _dense_formula(
            idx, q, wid, k, c)
        np.testing.assert_array_equal(got.ids, ids)
        np.testing.assert_array_equal(got.dists, dists)
        assert (got.stats.stop_level, got.stats.n_checked,
                got.stats.n_collisions, got.stats.found_k) == (
            stop, n_checked, n_coll, found_k)


def test_self_query_finds_itself(built):
    """A query that IS a data point must return it at distance ~0."""
    idx, _ = built
    for pid in (0, 100, 999):
        res = idx.search(idx.data[pid], weight_id=0, k=1)
        assert res.ids[0] == pid
        assert res.dists[0] < 1e-6


def test_io_accounting(built):
    idx, qs = built
    res = idx.search(qs.points[0], weight_id=int(qs.weight_ids[0]), k=5)
    st = res.stats
    assert st.io_blocks > 0
    assert st.n_checked <= 5 + int(np.ceil(idx.cfg.gamma * idx.n)) + 5
    assert st.n_collisions >= st.n_checked  # identify >= check


def test_c2lsh_degeneration():
    """WLSH with |S| = 1 is exactly C2LSH (shared plumbing, Eqs. 4-5)."""
    data = make_dataset(n=3_000, d=16, seed=21)
    w = np.ones(16)
    cfg = PlanConfig(p=2.0, c=3, n=len(data), gamma_n=100.0)
    c2 = C2LSH(data, cfg, weight=w, seed=5)
    wl = WLSHIndex(data, w[None, :], cfg, tau=float("inf"), seed=5)
    assert len(wl.part.groups) == 1
    # identical plans: same beta, mu
    assert c2.part.groups[0].beta_group == wl.part.groups[0].beta_group
    np.testing.assert_allclose(
        c2.part.groups[0].mus, wl.part.groups[0].mus
    )
    q = data[7].astype(np.float32) + 1.5
    r1 = c2.query(q, k=3)
    r2 = wl.search(q, weight_id=0, k=3)
    np.testing.assert_array_equal(r1.ids, r2.ids)


def test_collision_threshold_reduction_cuts_io():
    """Sec 4.2.1: reduced mu identifies candidates earlier -> fewer blocks."""
    data = make_dataset(n=2_000, d=16, seed=31)
    weights = make_weight_set(size=6, d=16, n_subset=2, n_subrange=10, seed=32)
    cfg = PlanConfig(p=2.0, c=3, n=len(data), gamma_n=100.0)
    io = {}
    for red in (True, False):
        idx = WLSHIndex(data, weights, cfg, tau=500.0, v=4, v_prime=4,
                        use_reduction=red, seed=7)
        qs = make_query_set(data, weights, n_query_points=6,
                            n_query_weights=2, seed=33)
        costs = [
            idx.search(q, weight_id=int(w), k=3).stats.io_blocks
            for q in qs.points for w in qs.weight_ids
        ]
        io[red] = float(np.mean(costs))
    assert io[True] <= io[False] * 1.25  # reduction must not blow up I/O


def test_non_integer_c_rejected():
    data = make_dataset(n=100, d=8, seed=0)
    with pytest.raises(ValueError):
        WLSHIndex(data, np.ones((1, 8)), PlanConfig(p=2.0, c=2.5, n=100),
                  tau=1e9)


def test_weight_set_generator_properties():
    W = make_weight_set(size=20, d=12, n_subset=4, n_subrange=5, seed=1)
    assert W.shape == (20, 12)
    assert np.all(W >= 1.0) and np.all(W <= 10.0)
    # subsets of 5 share a subrange per dim: within-subset spread is bounded
    for s in range(4):
        sub = W[s * 5 : (s + 1) * 5]
        assert np.all(sub.max(axis=0) - sub.min(axis=0) <= 9.0 / 5 + 1e-9)


# ------------------------------------------------------ lazy sorted tables

def _lazy_eager(p, tau, materialize):
    data = make_dataset(n=2_000, d=16, seed=31)
    weights = make_weight_set(size=8, d=16, n_subset=2, n_subrange=10,
                              seed=32)
    cfg = PlanConfig(p=p, c=3, n=len(data), gamma_n=100.0)
    return WLSHIndex(data, weights, cfg, tau=tau, v=4, v_prime=4, seed=33,
                     materialize=materialize)


def _tabled_groups(idx):
    return sorted(gi for gi, b in idx._built.items() if b.tables is not None)


def test_export_builds_no_sorted_tables():
    """The serving-plan export hashes every group but sorts none."""
    idx = _lazy_eager(2.0, 500.0, materialize=False)
    idx.export_serving_plan()
    assert sorted(idx._built) == list(range(len(idx.part.groups)))
    assert _tabled_groups(idx) == []


def test_host_search_builds_only_its_groups_tables():
    idx = _lazy_eager(2.0, 500.0, materialize=False)
    assert len(idx.part.groups) > 1
    idx.export_serving_plan()
    wid = 0
    idx.search_dense(idx.data[0], weight_id=wid, k=3)
    assert _tabled_groups(idx) == [int(idx.part.group_of[wid])]
    tables = idx._built[int(idx.part.group_of[wid])].tables
    idx.search(idx.data[1], weight_id=wid, k=3)
    assert idx._built[int(idx.part.group_of[wid])].tables is tables


def test_materialize_and_reset_build_every_groups_tables():
    idx = _lazy_eager(2.0, 500.0, materialize=True)
    assert _tabled_groups(idx) == list(range(len(idx.part.groups)))
    idx._built = {}
    idx.search(idx.data[0], weight_id=0, k=3)
    assert _tabled_groups(idx) == [int(idx.part.group_of[0])]


@pytest.mark.parametrize("p,tau", [(2.0, 500.0), (1.0, 1_000.0),
                                   (0.5, 2_000.0)])
def test_exported_codes_match_materialized(p, tau):
    lazy = _lazy_eager(p, tau, materialize=False).export_serving_plan()
    eager = _lazy_eager(p, tau, materialize=True).export_serving_plan()
    assert len(lazy.groups) == len(eager.groups)
    for a, b in zip(lazy.groups, eager.groups):
        assert a.codes.dtype == b.codes.dtype
        np.testing.assert_array_equal(a.codes, b.codes)
        np.testing.assert_array_equal(a.proj, b.proj)


@pytest.mark.parametrize("p,tau", [(2.0, 500.0), (1.0, 1_000.0),
                                   (0.5, 2_000.0)])
def test_lazy_and_eager_search_agree(p, tau):
    """Host answers and stats do not depend on when the tables are built."""
    lazy = _lazy_eager(p, tau, materialize=False)
    lazy.export_serving_plan()
    eager = _lazy_eager(p, tau, materialize=True)
    rng = np.random.default_rng(34)
    for qi in range(12):
        q = lazy.data[rng.integers(lazy.n)] + rng.normal(0, 30.0, 16)
        wid = int(rng.integers(len(lazy.weights)))
        k = (1, 5, 10)[qi % 3]
        for name in ("search", "search_dense"):
            a = getattr(lazy, name)(q, weight_id=wid, k=k)
            b = getattr(eager, name)(q, weight_id=wid, k=k)
            np.testing.assert_array_equal(a.ids, b.ids)
            np.testing.assert_array_equal(a.dists, b.dists)
            # assert_equal treats search_dense's NaN io_blocks as equal
            np.testing.assert_equal(dataclasses.asdict(a.stats),
                                    dataclasses.asdict(b.stats))
