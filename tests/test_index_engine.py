"""Sharded WLSH query engine vs the host oracle (WLSHIndex.search_dense).

Single-device mesh here; the multi-device SPMD semantics are covered by
tests/test_multidevice.py (subprocess with forced host device count) and by
the production dry-run.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.datagen import make_dataset, make_weight_set
from repro.core.params import PlanConfig
from repro.core.wlsh import WLSHIndex
from repro.index import (
    IndexConfig,
    build_state,
    encode_queries,
    make_query_step,
    pad_beta,
    pad_levels,
)
from repro.kernels import platform as kplatform


@pytest.fixture(scope="module")
def setup():
    data = make_dataset(n=1_024, d=16, seed=41)
    weights = make_weight_set(size=6, d=16, n_subset=2, n_subrange=10, seed=42)
    cfg = PlanConfig(p=2.0, c=3, n=len(data), gamma_n=100.0)
    host = WLSHIndex(data, weights, cfg, tau=500.0, v=4, v_prime=4, seed=9)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    return data, weights, cfg, host, mesh


def _engine_for_group(host: WLSHIndex, mesh, gi: int, data, k: int):
    built = host._group(gi)
    plan = built.plan
    n_levels = int(np.max(plan.n_levels))
    icfg = IndexConfig(
        n=len(data),
        d=data.shape[1],
        beta=built.fam.beta,
        q_batch=4,
        k=k,
        c=int(round(host.cfg.c)),
        n_levels=n_levels,
        p=host.cfg.p,
        block_n=256,
        gamma_n=host.cfg.gamma_n,
        vec_dtype="float32",
    )
    state = build_state(mesh, icfg, data, built.fam)
    step = make_query_step(mesh, icfg)
    return icfg, state, step, built


def test_engine_matches_host_oracle(setup):
    data, weights, cfg, host, mesh = setup
    k = 5
    gi = int(host.part.group_of[0])
    icfg, state, step, built = _engine_for_group(host, mesh, gi, data, k)

    # queries under every weight vector served by this group
    wids = [int(w) for w in built.plan.member_ids[:4]]
    nq = len(wids)
    rng = np.random.default_rng(43)
    qpts = data[rng.choice(len(data), nq, replace=False)].astype(np.float32)
    qpts += rng.normal(0, 3.0, qpts.shape).astype(np.float32)

    q_weight = np.stack([host.weights[w] for w in wids]).astype(np.float32)
    mus, r_mins, betas, levels = [], [], [], []
    for w in wids:
        _, slot, beta_i, mu_i = host._member_params(w)
        mus.append(mu_i)
        r_mins.append(built.plan.r_min_members[slot])
        betas.append(beta_i)
        levels.append(int(built.plan.n_levels[slot]))

    dists, ids, stop, n_checked = step(
        state,
        jnp.asarray(qpts),
        encode_queries(state, qpts),
        jnp.asarray(q_weight),
        jnp.asarray(mus, jnp.int32),
        jnp.asarray(r_mins, jnp.float32),
        jnp.asarray(betas, jnp.int32),
        jnp.asarray(levels, jnp.int32),
        jnp.int32(nq),
    )
    dists, ids, stop = np.asarray(dists), np.asarray(ids), np.asarray(stop)

    for qi, wid in enumerate(wids):
        want = host.search_dense(qpts[qi], weight_id=wid, k=k)
        assert stop[qi] == want.stats.stop_level, (
            f"stop level mismatch q{qi}: {stop[qi]} vs {want.stats.stop_level}"
        )
        got_ids = ids[qi][ids[qi] >= 0]
        want_ids = want.ids[want.ids >= 0]
        # The engine hashes queries in f32, the host oracle in f64; near-
        # boundary code jitter can flip individual candidates near the mu
        # threshold.  Demand strong agreement, not identity:
        overlap = len(set(got_ids) & set(want_ids))
        assert overlap >= max(1, (min(len(got_ids), len(want_ids)) + 1) // 2)
        # ... and guarantee-level agreement on the best distance
        assert dists[qi][0] <= host.cfg.c * max(want.dists[0], 1e-9) + 1e-6


def test_engine_self_query(setup):
    data, weights, cfg, host, mesh = setup
    gi = int(host.part.group_of[0])
    icfg, state, step, built = _engine_for_group(host, mesh, gi, data, k=1)
    wid = int(built.plan.member_ids[0])
    _, slot, beta_i, mu_i = host._member_params(wid)
    pids = [0, 17, 1023, 512]
    qpts = jnp.asarray(data[pids], jnp.float32)
    dists, ids, *_ = step(
        state,
        qpts,
        encode_queries(state, qpts),
        jnp.asarray(np.stack([host.weights[wid]] * 4), jnp.float32),
        jnp.asarray([mu_i] * 4, jnp.int32),
        jnp.asarray([built.plan.r_min_members[slot]] * 4, jnp.float32),
        jnp.asarray([beta_i] * 4, jnp.int32),
        jnp.asarray([int(built.plan.n_levels[slot])] * 4, jnp.int32),
        jnp.int32(4),
    )
    np.testing.assert_array_equal(np.asarray(ids)[:, 0], pids)
    assert np.all(np.asarray(dists)[:, 0] < 1e-3)


def _step_args(host, built, state, data, wids, seed):
    """A query step's inputs after ``state`` and before ``n_live``: noisy
    corpus rows as queries, one per member id in ``wids``."""
    rng = np.random.default_rng(seed)
    qpts = data[rng.choice(len(data), len(wids), replace=False)].astype(
        np.float32)
    qpts += rng.normal(0, 3.0, qpts.shape).astype(np.float32)
    mus, r_mins, betas, levels = [], [], [], []
    for w in wids:
        _, slot, beta_i, mu_i = host._member_params(w)
        mus.append(mu_i)
        r_mins.append(built.plan.r_min_members[slot])
        betas.append(beta_i)
        levels.append(int(built.plan.n_levels[slot]))
    return (
        jnp.asarray(qpts),
        encode_queries(state, qpts),
        jnp.asarray(np.stack([host.weights[w] for w in wids]), jnp.float32),
        jnp.asarray(mus, jnp.int32),
        jnp.asarray(r_mins, jnp.float32),
        jnp.asarray(betas, jnp.int32),
        jnp.asarray(levels, jnp.int32),
    )


@pytest.mark.parametrize("block_n", [256, 512],
                         ids=["fixture_block", "two_tile_blocks"])
def test_engine_fused_paths_bit_exact(setup, monkeypatch, block_n):
    """The Pallas kernel body (interpret mode) inside the engine must be
    bit-exact with the fused XLA composite the CPU serves: same ids,
    dists, stop levels and n_checked.  The exact re-rank plus identical
    candidate sets absorb any kernel-internal float jitter, so equality
    is exact, not approximate.  Both block sizes split the group's 1,024
    rows into several scan blocks: four of one kernel row tile each, and
    two of two tiles each."""
    data, weights, cfg, host, mesh = setup
    k = 5
    gi = int(host.part.group_of[0])
    icfg, _, _, built = _engine_for_group(host, mesh, gi, data, k)
    icfg = dataclasses.replace(icfg, block_n=block_n)
    state = build_state(mesh, icfg, data, built.fam)

    wids = [int(w) for w in built.plan.member_ids[:4]]
    args = (*_step_args(host, built, state, data, wids, seed=47),
            jnp.int32(len(wids)))
    assert kplatform.scan_mode() == "xla"
    want = make_query_step(mesh, icfg)(state, *args)

    monkeypatch.setattr(kplatform, "scan_mode", lambda: "interpret")
    got = make_query_step(mesh, icfg)(state, *args)

    for name, a, b in zip(("dists", "ids", "stop", "n_checked"), want, got):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"Pallas interpret diverged from the composite on {name}",
        )


def test_engine_refuses_a_block_that_does_not_divide_the_shard(setup):
    """A block_n that leaves a tail of the shard's rows unscanned is refused
    when the step is traced, not answered wrongly."""
    data, weights, cfg, host, mesh = setup
    gi = int(host.part.group_of[0])
    icfg, state, _, built = _engine_for_group(host, mesh, gi, data, k=5)
    icfg = dataclasses.replace(icfg, block_n=384)
    wids = [int(w) for w in built.plan.member_ids[:4]]
    args = (*_step_args(host, built, state, data, wids, seed=59),
            jnp.int32(len(wids)))
    with pytest.raises(ValueError, match="does not divide"):
        jax.eval_shape(make_query_step(mesh, icfg), state, *args)


@pytest.mark.parametrize("n_live", [1, 3])
def test_engine_n_live_answers_live_rows_as_a_full_batch(setup, monkeypatch,
                                                        n_live):
    """A Pallas (interpret) step told that only ``n_live`` of its Q rows are
    live answers those rows exactly as the full batch does: same ids,
    dists, stop levels and n_checked.  The skipped rows come back with
    empty histograms (n_checked 0) and no candidates (dists +inf)."""
    data, weights, cfg, host, mesh = setup
    k = 5
    gi = int(host.part.group_of[0])
    monkeypatch.setattr(kplatform, "scan_mode", lambda: "interpret")
    icfg, state, step, built = _engine_for_group(host, mesh, gi, data, k)

    members = built.plan.member_ids
    wids = [int(members[i % len(members)]) for i in range(icfg.q_batch)]
    args = _step_args(host, built, state, data, wids, seed=53)
    full = step(state, *args, jnp.int32(icfg.q_batch))
    part = step(state, *args, jnp.int32(n_live))
    for name, a, b in zip(("dists", "ids", "stop", "n_checked"), full, part):
        np.testing.assert_array_equal(
            np.asarray(a)[:n_live], np.asarray(b)[:n_live],
            err_msg=f"live rows diverged under n_live={n_live} on {name}")
    assert np.all(np.isposinf(np.asarray(part[0])[n_live:]))
    assert np.all(np.asarray(part[3])[n_live:] == 0)


def test_budget_derived_from_gamma():
    # paper default: budget = k + ceil(gamma * n) with gamma = gamma_n / n
    cfg = IndexConfig(n=2_000, k=7, gamma_n=100.0)
    assert cfg.gamma == 100.0 / 2_000
    assert cfg.budget == 7 + 100
    cfg = IndexConfig(n=1 << 30, k=10, gamma_n=100.0)
    assert cfg.budget == 110
    # explicit override wins (the practical choice at 1B points)
    cfg = IndexConfig(n=1 << 30, k=10, budget_override=4096)
    assert cfg.budget == 4096
    # engine and host planner agree by construction
    from repro.core.params import PlanConfig

    pcfg = PlanConfig(n=4_000, gamma_n=100.0)
    icfg = IndexConfig(n=4_000, k=5, gamma_n=pcfg.gamma_n)
    assert icfg.budget == 5 + int(np.ceil(pcfg.gamma * pcfg.n))


def test_shape_padding_buckets():
    assert pad_beta(1) == 32
    assert pad_beta(135) == 160
    assert pad_beta(160) == 160
    assert pad_beta(161) == 192
    assert pad_beta(513) == 1024
    assert pad_beta(150, buckets=(128, 256)) == 256
    with pytest.raises(ValueError):
        pad_beta(300, buckets=(128, 256))
    assert pad_levels(13) == 16
    assert pad_levels(16) == 16
    assert pad_levels(5, step=8) == 8
    # configs built from shapes that quantize to the same buckets are equal
    # (and therefore share one compiled step through QueryStepCache)
    a = IndexConfig(n=1_024, beta=pad_beta(135), n_levels=pad_levels(13))
    b = IndexConfig(n=1_024, beta=pad_beta(137), n_levels=pad_levels(14))
    assert a == b and a.shape_signature() == b.shape_signature()


def test_build_is_deterministic(setup):
    data, weights, cfg, host, mesh = setup
    gi = int(host.part.group_of[0])
    built = host._group(gi)
    icfg = IndexConfig(n=len(data), d=data.shape[1], beta=built.fam.beta,
                       vec_dtype="float32")
    s1 = build_state(mesh, icfg, data, built.fam)
    s2 = build_state(mesh, icfg, data, built.fam)
    np.testing.assert_array_equal(np.asarray(s1.codes), np.asarray(s2.codes))
    # codes agree with the host planner's (float64) oracle except at rare
    # f32-vs-f64 floor boundaries (projection magnitudes reach ~r_max/w, so
    # f32 ulp jitter near bucket edges flips ~0.5% of codes by exactly one —
    # noise on top of the random hash, bounded and harmless); the state
    # stores them point axis minor, (beta, n)
    assert s1.codes.shape == (built.fam.beta, len(data))
    assert s1.points.shape == (data.shape[1], len(data))
    host_codes = built.codes.T
    mismatch = np.mean(np.asarray(s1.codes) != host_codes)
    assert mismatch < 2e-2
    assert np.max(np.abs(np.asarray(s1.codes) - host_codes)) <= 1
