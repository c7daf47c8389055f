"""Multi-group retrieval service vs the host oracle, and the batching core.

The service must route every query to its weight's table group, answer a
mixed batch spanning >= 3 groups *identically* to `WLSHIndex.search_dense`
for every supported exponent p in {2, 1, 0.5} (the plan ships host codes
and the service host-encodes queries in f64, so candidate sets match
bit-exactly; distances compare in f32), coalesce and pad batches without
changing per-query answers, and compile at most one query step per
distinct padded shape signature.

The shared batching core (`serving.batching`) is additionally pinned by
hypothesis property tests against a fake executor: arbitrary interleavings
of group ids and ragged tails always merge back in submission order with
no dropped or duplicated query, and padded rows never leak into results.
"""

from __future__ import annotations

import numpy as np
import pytest

from _hyp import given, settings, st
from conftest import build_parity_service
from repro.core.serving_plan import ServingPlan
from repro.serving import RetrievalService, ServiceConfig
from repro.serving.batching import coalesce, pad_take, run_plans

K = 5


@pytest.fixture(scope="module")
def setup():
    # the p=2 instance of the session parity build (betas 135/135/137/161
    # at these seeds); structure tests share it with the parity suite
    return build_parity_service(2.0)[1:]


def _mixed_queries(data, weights, n_queries, seed=43):
    rng = np.random.default_rng(seed)
    wids = rng.integers(0, len(weights), n_queries)
    qpts = data[rng.choice(len(data), n_queries, replace=False)].astype(
        np.float32
    )
    qpts += rng.normal(0, 3.0, qpts.shape).astype(np.float32)
    return qpts, wids


def test_routing_follows_partition(setup):
    data, weights, host, plan, svc = setup
    qpts, wids = _mixed_queries(data, weights, 16)
    res = svc.query(qpts, wids)
    np.testing.assert_array_equal(
        res.group_ids, host.part.group_of[wids].astype(np.int32)
    )
    # distinct member parameters across the served groups
    betas = {int(g.beta_group) for g in plan.groups}
    mus = {tuple(g.mu_members.tolist()) for g in plan.groups}
    assert len(betas) >= 2 and len(mus) >= 3


def test_mixed_batch_matches_search_dense(parity_setup):
    """Bit-exact ids/stop/n_checked vs the host oracle, per p in {2, 1, 0.5}."""
    p, data, weights, host, plan, svc = parity_setup
    qpts, wids = _mixed_queries(data, weights, 24)
    res = svc.query(qpts, wids)
    assert len(np.unique(res.group_ids)) >= 3
    for qi in range(len(qpts)):
        want = host.search_dense(qpts[qi], weight_id=int(wids[qi]), k=K)
        np.testing.assert_array_equal(
            res.ids[qi], want.ids.astype(np.int32),
            err_msg=f"ids mismatch at query {qi} (weight {wids[qi]}, p={p})",
        )
        assert int(res.stop_levels[qi]) == want.stats.stop_level
        assert int(res.n_checked[qi]) == want.stats.n_checked
        m = res.ids[qi] >= 0
        np.testing.assert_allclose(
            res.dists[qi][m], want.dists[m], rtol=1e-4, atol=1e-2
        )


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_capacity_in_whole_tiles_answers_exactly(monkeypatch, mode):
    """A 1,000-row corpus (not a whole 256-row tile) is stored point axis
    minor at a capacity of 1,024 rows, (beta, 1024) codes and (16, 1024)
    vectors with 24 dead rows past ``n_valid``, and both scan routes (the
    CPU composite and the Pallas kernels, interpreted) answer bit for bit
    as ``search_dense`` does: ids, stop levels and n_checked."""
    from repro.core.datagen import make_dataset, make_weight_set
    from repro.core.params import PlanConfig
    from repro.core.wlsh import WLSHIndex
    from repro.kernels import platform as kplatform

    monkeypatch.setattr(kplatform, "scan_mode", lambda: mode)
    data = make_dataset(n=1_000, d=16, seed=41)
    weights = make_weight_set(size=8, d=16, n_subset=4, n_subrange=10,
                              seed=42)
    host = WLSHIndex(data, weights,
                     PlanConfig(p=2.0, c=3, n=len(data), gamma_n=100.0),
                     tau=500.0, v=4, v_prime=4, seed=9)
    plan = host.export_serving_plan()
    svc = RetrievalService(plan, data, cfg=ServiceConfig(k=K, q_batch=4))
    assert plan.n == 1_000 and svc.batcher.row_capacity() == 1_024
    qpts, wids = _mixed_queries(data, weights, 12)
    res = svc.query(qpts, wids)
    assert len(np.unique(res.group_ids)) >= 2
    for gi in np.unique(res.group_ids):
        with svc.state_cache.lease(int(gi)) as st:
            assert st.codes.shape == (svc.group_config(int(gi)).beta, 1_024)
            assert st.points.shape == (16, 1_024)
            assert int(st.n_valid) == 1_000
    for qi in range(len(qpts)):
        want = host.search_dense(qpts[qi], weight_id=int(wids[qi]), k=K)
        np.testing.assert_array_equal(res.ids[qi], want.ids.astype(np.int32))
        assert int(res.stop_levels[qi]) == want.stats.stop_level
        assert int(res.n_checked[qi]) == want.stats.n_checked
        m = res.ids[qi] >= 0
        np.testing.assert_allclose(res.dists[qi][m], want.dists[m],
                                   rtol=1e-4, atol=1e-2)


def test_one_compiled_step_per_shape_signature(setup):
    data, weights, host, plan, svc = setup
    svc.warmup()  # every group built + compiled
    signatures = {
        svc.group_config(gi).shape_signature()
        for gi in range(plan.n_groups)
    }
    assert svc.step_cache.n_compiled == len(signatures)
    # bucketed padding makes sharing actually happen on this plan
    assert svc.step_cache.n_compiled < plan.n_groups
    # repeated traffic compiles nothing new
    qpts, wids = _mixed_queries(data, weights, 8, seed=5)
    before = svc.step_cache.n_compiled
    svc.query(qpts, wids)
    assert svc.step_cache.n_compiled == before


def test_coalesced_batch_equals_one_at_a_time(setup):
    data, weights, host, plan, svc = setup
    # all queries under weights of one group -> coalesced into shared batches
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    members = plan.groups[gi].member_ids
    rng = np.random.default_rng(7)
    wids = members[rng.integers(0, len(members), 6)]
    qpts = data[rng.choice(len(data), 6, replace=False)].astype(np.float32)
    qpts += rng.normal(0, 3.0, qpts.shape).astype(np.float32)

    batched = svc.query(qpts, wids)
    assert np.all(batched.group_ids == gi)
    for qi in range(len(qpts)):
        single = svc.query(qpts[qi : qi + 1], wids[qi : qi + 1])
        np.testing.assert_array_equal(single.ids[0], batched.ids[qi])
        np.testing.assert_array_equal(single.dists[0], batched.dists[qi])
        assert single.stop_levels[0] == batched.stop_levels[qi]
        assert single.n_checked[0] == batched.n_checked[qi]


def test_ragged_batches_match_aligned(setup):
    data, weights, host, plan, svc = setup
    # 13 mixed queries with q_batch=4 -> every group serves a padded tail
    qpts, wids = _mixed_queries(data, weights, 13, seed=11)
    ragged = svc.query(qpts, wids)
    # same queries submitted one by one (maximal padding, 1/4 occupancy)
    for qi in range(len(qpts)):
        single = svc.query(qpts[qi : qi + 1], wids[qi : qi + 1])
        np.testing.assert_array_equal(single.ids[0], ragged.ids[qi])
        np.testing.assert_array_equal(single.dists[0], ragged.dists[qi])


def test_run_batch_launches_with_the_real_row_count(setup, monkeypatch):
    """``run_batch`` pads a ragged batch to ``q_batch`` and tells the step
    how many rows are real: its last input, ``n_live``, is the count."""
    data, weights, host, plan, svc = setup
    batcher = svc.batcher
    get = batcher.step_cache.get
    seen = []

    def spy_get(mesh, cfg):
        step = get(mesh, cfg)

        def spy(*args):
            seen.append((len(args[1]), int(args[-1])))
            return step(*args)

        return spy

    monkeypatch.setattr(batcher.step_cache, "get", spy_get)
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    members = plan.groups[gi].member_ids
    qb = svc.cfg.q_batch
    for real in sorted({1, qb - 1, qb}):
        ids, *_ = batcher.run_batch(gi, data[:real],
                                    members[np.arange(real) % len(members)])
        assert len(ids) == real
        assert seen[-1] == (qb, real)


def test_serving_stats_accounting(setup):
    data, weights, host, plan, svc = setup
    svc.reset_stats()
    qpts, wids = _mixed_queries(data, weights, 13, seed=11)
    res = svc.query(qpts, wids)
    summary = svc.stats_summary()
    assert sum(s["n_queries"] for s in summary.values()) == 13
    for gi, s in summary.items():
        served = int(np.sum(res.group_ids == gi))
        assert s["n_queries"] == served
        assert 0.0 < s["occupancy"] <= 1.0
        assert s["n_batches"] == -(-served // svc.cfg.q_batch)


def test_plan_npz_roundtrip(tmp_path, setup):
    data, weights, host, plan, svc = setup
    path = str(tmp_path / "plan.npz")
    plan.save_npz(path)
    plan2 = ServingPlan.load_npz(path)
    assert plan2.n_groups == plan.n_groups
    assert (plan2.n, plan2.d, plan2.c, plan2.p) == (
        plan.n, plan.d, plan.c, plan.p
    )
    np.testing.assert_array_equal(plan2.group_of, plan.group_of)
    np.testing.assert_array_equal(plan2.weights, plan.weights)
    for a, b in zip(plan.groups, plan2.groups):
        np.testing.assert_array_equal(a.proj, b.proj)
        np.testing.assert_array_equal(a.codes, b.codes)
        np.testing.assert_array_equal(a.mu_members, b.mu_members)
        np.testing.assert_array_equal(a.r_min_members, b.r_min_members)
        assert a.width == b.width and a.levels_cap == b.levels_cap
    # a service over the reloaded plan answers identically
    svc2 = RetrievalService(plan2, data, cfg=ServiceConfig(k=K, q_batch=4))
    qpts, wids = _mixed_queries(data, weights, 6, seed=3)
    r1, r2 = svc.query(qpts, wids), svc2.query(qpts, wids)
    np.testing.assert_array_equal(r1.ids, r2.ids)
    np.testing.assert_array_equal(r1.dists, r2.dists)


def test_plan_npz_roundtrip_preserves_dtypes_exactly(tmp_path, setup):
    """Regression guard for the offload/restore path: every array of a
    saved-and-reloaded ServingPlan must keep its exact dtype and bytes
    (r_min stays f64, codes stay i32, ...), scalars their python types,
    and optional host codes must round-trip both present and absent."""
    import dataclasses

    data, weights, host, plan, svc = setup
    path = str(tmp_path / "plan_dtypes.npz")
    plan.save_npz(path)
    plan2 = ServingPlan.load_npz(path)
    for f in ("weights", "group_of", "member_slot"):
        a, b = getattr(plan, f), getattr(plan2, f)
        assert a.dtype == b.dtype, f"plan.{f} dtype drifted"
        np.testing.assert_array_equal(a, b)
    for f in ("n", "d", "c"):
        assert isinstance(getattr(plan2, f), int)
    for f in ("p", "gamma_n", "tau"):
        assert isinstance(getattr(plan2, f), float)
        assert getattr(plan2, f) == getattr(plan, f)
    for g, g2 in zip(plan.groups, plan2.groups):
        for fld in dataclasses.fields(g):
            a, b = getattr(g, fld.name), getattr(g2, fld.name)
            if isinstance(a, np.ndarray):
                assert a.dtype == b.dtype, (
                    f"group.{fld.name} dtype drifted: {a.dtype} -> {b.dtype}"
                )
                np.testing.assert_array_equal(
                    a, b, err_msg=f"group.{fld.name} values drifted"
                )
            else:
                assert type(a) is type(b) and a == b, f"group.{fld.name}"
    # optional host codes absent: stays absent through the round-trip
    plan_nc = host.export_serving_plan(include_codes=False)
    path_nc = str(tmp_path / "plan_nocodes.npz")
    plan_nc.save_npz(path_nc)
    plan_nc2 = ServingPlan.load_npz(path_nc)
    assert all(g.codes is None for g in plan_nc2.groups)


def test_plan_without_codes_serves_via_device_encoding(setup):
    """include_codes=False: data codes are built on device (f32), so query
    codes must come from the same encoding — the service falls back from
    host_encode automatically and self-queries still find themselves."""
    data, weights, host, plan, svc = setup
    plan2 = host.export_serving_plan(include_codes=False)
    assert all(g.codes is None for g in plan2.groups)
    svc2 = RetrievalService(plan2, data, cfg=ServiceConfig(k=K, q_batch=4))
    rng = np.random.default_rng(13)
    wids = rng.integers(0, len(weights), 4)
    res = svc2.query(data[:4].astype(np.float32), wids)
    np.testing.assert_array_equal(res.ids[:, 0], np.arange(4))
    assert np.all(res.dists[:, 0] < 1e-3)


def test_weight_id_validation(setup):
    data, weights, host, plan, svc = setup
    q = data[:1].astype(np.float32)
    with pytest.raises(ValueError):
        svc.query(q, [len(weights)])
    with pytest.raises(ValueError):
        svc.query(q, [-1])
    with pytest.raises(ValueError):
        svc.query(data[:2].astype(np.float32), [0])


# ------------------------------------------------------- config validation


@pytest.mark.parametrize("kwargs", [
    dict(q_batch=0),
    dict(q_batch=-3),
    dict(k=0),
    dict(block_n=0),
    dict(level_step=0),
    dict(budget_override=0),
    dict(max_delay_ms=-1.0),
    dict(max_delay_ms=float("nan")),
    dict(beta_buckets=()),
    dict(beta_buckets=(0, 32)),
    dict(vec_dtype="not-a-dtype"),
])
def test_service_config_rejects_invalid(kwargs):
    with pytest.raises(ValueError):
        ServiceConfig(**kwargs)


def test_service_config_accepts_defaults_and_edges():
    ServiceConfig()  # defaults must validate
    ServiceConfig(q_batch=1, k=1, level_step=1, max_delay_ms=0.0,
                  block_n=1, budget_override=1, beta_buckets=(32, 512),
                  vec_dtype="bfloat16")


# ------------------------------- batching core properties (fake executor)


@st.composite
def _traffic_shape(draw):
    """Arbitrary interleaving of group ids plus a compiled batch size."""
    n_groups = draw(st.integers(1, 5))
    gids = draw(st.lists(st.integers(0, n_groups - 1), min_size=1,
                         max_size=48))
    q_batch = draw(st.integers(1, 9))
    return np.asarray(gids), q_batch


@given(_traffic_shape())
@settings(max_examples=100, deadline=None)
def test_coalesce_partitions_every_submission_once(traffic):
    gids, qb = traffic
    plans = coalesce(gids, qb)
    rows = np.concatenate([bp.rows for bp in plans])
    assert sorted(rows.tolist()) == list(range(len(gids)))  # no drop/dup
    for bp in plans:
        assert 1 <= len(bp.rows) <= qb
        assert np.all(gids[bp.rows] == bp.group_id)
        assert np.all(np.diff(bp.rows) > 0)  # submission order within batch
    for gi in np.unique(gids):
        served = int(np.sum(gids == gi))
        n_batches = sum(bp.group_id == gi for bp in plans)
        assert n_batches == -(-served // qb)  # minimal batch count


@given(st.integers(1, 9))
@settings(max_examples=50, deadline=None)
def test_pad_take_cycles_real_rows(qb):
    for real in range(1, qb + 1):
        take = pad_take(real, qb)
        assert take.shape == (qb,)
        np.testing.assert_array_equal(take[:real], np.arange(real))
        np.testing.assert_array_equal(take, np.arange(qb) % real)
    with pytest.raises(ValueError):
        pad_take(0, qb)
    with pytest.raises(ValueError):
        pad_take(qb + 1, qb)


@given(_traffic_shape())
@settings(max_examples=100, deadline=None)
def test_run_plans_merges_in_submission_order_without_pad_leak(traffic):
    """A fake executor tags each padded row; merged results must hold every
    submission's own tag exactly once and never a pad poison value."""
    gids, qb = traffic
    nq, k = len(gids), 3
    queries = np.arange(nq, dtype=np.float32).reshape(nq, 1)  # row tag
    wids = np.arange(nq)  # weight_ids double as submission indices
    pad_poison = -7
    reals = []

    def fake_run_batch(gi, qsub, wsub):
        real = len(qsub)
        assert 1 <= real <= qb
        assert np.all(gids[wsub] == gi)  # only rows routed to this group
        np.testing.assert_array_equal(qsub[:, 0].astype(np.int64), wsub)
        take = pad_take(real, qb)
        padded_rows = wsub[take]  # what the compiled step would see
        ids = np.repeat(padded_rows[:, None], k, 1).astype(np.int32)
        stop = padded_rows.astype(np.int32)
        ids[real:] = pad_poison  # poison pad outputs: must never merge
        stop[real:] = pad_poison
        reals.append(real)
        return (ids[:real], ids[:real].astype(np.float32),
                stop[:real], stop[:real])

    out_ids, out_d, out_stop, out_chk = run_plans(
        coalesce(gids, qb), queries, wids, fake_run_batch, k
    )
    want = np.repeat(np.arange(nq, dtype=np.int32)[:, None], k, 1)
    np.testing.assert_array_equal(out_ids, want)  # submission order kept
    np.testing.assert_array_equal(out_stop, np.arange(nq))
    np.testing.assert_array_equal(out_chk, np.arange(nq))
    assert not np.any(out_ids == pad_poison)
    assert not np.any(out_stop == pad_poison)
    assert sum(reals) == nq  # every query executed exactly once
