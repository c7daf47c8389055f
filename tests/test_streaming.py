"""Streaming inserts: delta segments, compaction parity, delete support.

The mutable subsystem must never change what the static stack pinned:

* pre-compaction recall — a query whose true nearest neighbor is an
  unsealed insert always returns it (the delta memtable is scanned
  exactly), property-tested; deleted ids never appear in any result;
* post-compaction parity — a group state reached by insert -> seal ->
  compact answers bit-exactly (ids/stop/n_checked) like
  ``WLSHIndex.search_dense`` on an index freshly built from the union
  corpus with the same family seeds, for p in {2, 1, 0.5}, on both
  frontends, paged and unpaged;
* compaction touches one group's cached state (versioned invalidation)
  and never a compiled step; discard-mode cold rebuilds include the
  compacted rows;
* the ``merge_topk`` helper preserves the no-drop/no-dup/no-tombstone
  merge invariants (property-tested on synthetic candidate lists).
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from _hyp import given, settings, st
from conftest import build_parity_service
from repro.core.serving_plan import ServingPlan
from repro.core.wlsh import WLSHIndex
from repro.serving import (
    AsyncRetrievalService,
    ManualClock,
    RetrievalService,
    ServiceConfig,
    merge_topk,
    replay_open_loop,
)

K = 5


def _streaming_service(plan, data, *, cap=None, reserve=64, seal_rows=8,
                       q_batch=4, auto=None, offload=True):
    svc = RetrievalService(
        plan, data,
        cfg=ServiceConfig(
            k=K, q_batch=q_batch, max_resident_groups=cap,
            delta_seal_rows=seal_rows, delta_reserve_rows=reserve,
            auto_compact_segments=auto, offload_evicted=offload,
        ),
    )
    svc.warmup()
    svc.reset_stats()
    return svc


@pytest.fixture(scope="module")
def setup():
    # p=2 instance of the session parity build; streaming tests construct
    # their own services over it (never mutate the shared one)
    return build_parity_service(2.0)[1:]


def _far_vector(data, i, tag):
    """A fresh insert guaranteed distinct from (and far from) the corpus."""
    return (data[i % len(data)] + 50_000.0 + 13.0 * tag).astype(np.float32)


# ------------------------------------------------------- pre-compaction reads


def test_insert_visible_immediately_and_tenant_scoped(setup):
    data, weights, host, plan, _ = setup
    svc = _streaming_service(plan, data)
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    w_in = int(plan.groups[gi].member_ids[0])
    v = _far_vector(data, 3, tag=1)
    pid = svc.insert(v, w_in)
    assert pid == plan.n  # ids continue from the corpus epoch
    res = svc.query(v[None], [w_in])
    assert res.ids[0][0] == pid and res.dists[0][0] == 0.0
    # inserts are tenant-scoped: a weight routed to a *different* group
    # does not see the row
    other = int(np.where(plan.group_of != gi)[0][0])
    res_other = svc.query(v[None], [other])
    assert pid not in res_other.ids[0]
    # and the indexed hits behind the delta hit are unperturbed
    base = svc.query(data[5][None].astype(np.float32), [w_in])
    assert pid not in base.ids[0][:1] or base.dists[0][0] == 0.0


def test_deleted_ids_never_appear(setup):
    data, weights, host, plan, _ = setup
    svc = _streaming_service(plan, data)
    q = data[11].astype(np.float32)
    wid = 0
    before = svc.query(q[None], [wid])
    victim = int(before.ids[0][0])
    svc.delete(victim)
    after = svc.query(q[None], [wid])
    assert victim not in after.ids[0]
    # backfill keeps the remaining candidates sorted with no duplicates
    valid = after.ids[0][after.ids[0] >= 0]
    assert len(set(valid.tolist())) == len(valid)
    d = after.dists[0]
    assert np.all(np.diff(d[np.isfinite(d)]) >= 0)
    # deleting an unknown id is rejected
    with pytest.raises(ValueError):
        svc.delete(10**9)


@st.composite
def _insert_case(draw):
    base = draw(st.integers(0, 1_023))
    tag = draw(st.integers(0, 500))
    wid = draw(st.integers(0, 7))
    deleted = draw(st.booleans())
    return base, tag, wid, deleted


@given(_insert_case())
@settings(max_examples=30, deadline=None)
def test_unsealed_insert_is_always_recalled_property(case):
    """Queries whose true nearest neighbor is an unsealed insert always
    return it (exact delta scan); once deleted it never appears.  State
    accumulates across examples — recall must survive a growing memtable
    and tombstone set."""
    base, tag, wid, deleted = case
    data, weights, host, plan, _ = build_parity_service(2.0)[1:]
    svc = _property_service(plan, data)
    # repeated (base, tag) draws must not produce duplicate vectors: a
    # distance-0 tie would resolve to the *earlier* example's id (stable
    # scan order), which is correct recall but not what this asserts —
    # an all-dims serial offset keeps every insert unique under any
    # member weight
    _property_cache["serial"] = _property_cache.get("serial", 0) + 1
    v = _far_vector(data, base, tag) + np.float32(
        997.0 * _property_cache["serial"]
    )
    pid = svc.insert(v, wid)
    if deleted:
        svc.delete(pid)
    res = svc.query(v[None], [wid])
    if deleted:
        assert pid not in res.ids[0]
    else:
        assert res.ids[0][0] == pid and res.dists[0][0] == 0.0


_property_cache: dict = {}


def _property_service(plan, data):
    # one shared service across hypothesis examples: large seal threshold
    # keeps every insert in the open memtable (the "unsealed" regime)
    if "svc" not in _property_cache:
        _property_cache["svc"] = _streaming_service(
            plan, data, seal_rows=10_000, reserve=0
        )
    return _property_cache["svc"]


# ------------------------------------------------------- seal / compact flow


def test_seal_and_auto_compact_lifecycle(setup):
    data, weights, host, plan, _ = setup
    svc = _streaming_service(plan, data, seal_rows=4, auto=1)
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    w_in = int(plan.groups[gi].member_ids[0])
    pids = [svc.insert(_far_vector(data, j, 7), w_in) for j in range(4)]
    d = svc.delta_summary()
    assert d["n_seals"] == 1 and d["n_compactions"] == 1
    assert d["n_rows_compacted"] == 4 and d["n_pending"] == 0
    assert d["plan_version"] == 1
    assert d["corpus_epoch"] == plan.n + 4
    # versioned invalidation: exactly the compacted group, nobody else
    assert svc.state_cache.version_of(gi) == 1
    assert all(
        svc.state_cache.version_of(g) == 0
        for g in range(plan.n_groups) if g != gi
    )
    assert svc.cache_summary()["n_invalidations"] == 1
    assert svc.stats[gi].n_state_invalidations == 1
    # compacted rows now served by the compiled index path
    for j, pid in enumerate(pids):
        res = svc.query(_far_vector(data, j, 7)[None], [w_in])
        assert res.ids[0][0] == pid and res.dists[0][0] == 0.0


def test_compaction_never_recompiles(setup):
    """Acceptance: QueryStepCache counters pinned across seal/compact."""
    data, weights, host, plan, _ = setup
    svc = _streaming_service(plan, data, seal_rows=4)
    signatures = {
        svc.group_config(gi).shape_signature()
        for gi in range(plan.n_groups)
    }
    assert svc.step_cache.n_compiled == len(signatures)
    w_in = int(plan.groups[0].member_ids[0])
    for j in range(9):  # 2 seals + a partial memtable
        svc.insert(_far_vector(data, j, 3), w_in)
    assert svc.delta_summary()["n_seals"] == 2
    assert svc.step_cache.n_compiled == len(signatures)
    assert svc.compact() == 9
    assert svc.step_cache.n_compiled == len(signatures)
    rng = np.random.default_rng(3)
    wids = rng.integers(0, len(weights), 8)
    qpts = data[rng.choice(len(data), 8, replace=False)].astype(np.float32)
    svc.query(qpts, wids)  # post-compaction traffic over every group
    assert svc.step_cache.n_compiled == len(signatures)


def test_capacity_exhaustion_is_explicit(setup):
    data, weights, host, plan, _ = setup
    svc = _streaming_service(plan, data, reserve=4, seal_rows=2)
    w_in = int(plan.groups[0].member_ids[0])
    # the capacity holds the reserve rounded up to a whole 256-row tile
    room = svc.batcher.row_capacity() - plan.n
    assert 4 <= room < 4 + 256
    pids = [svc.insert(_far_vector(data, j, 9), w_in)
            for j in range(room + 2)]
    # the background (non-strict) path skips the over-capacity group...
    assert svc.batcher.delta.compact_sealed() == 0
    # ...while the explicit path names the fix
    with pytest.raises(ValueError, match="delta_reserve_rows"):
        svc.compact()
    # rows keep serving from the exact scan regardless
    res = svc.query(_far_vector(data, 2, 9)[None], [w_in])
    assert res.ids[0][0] == pids[2]


def test_cold_rebuild_includes_compacted_rows(setup):
    """Discard-mode paging must rebuild a compacted group from its union
    corpus — eviction can never silently drop streamed rows."""
    data, weights, host, plan, _ = setup
    svc = _streaming_service(plan, data, cap=1, offload=False,
                             seal_rows=4, reserve=64)
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    w_in = int(plan.groups[gi].member_ids[0])
    pids = [svc.insert(_far_vector(data, j, 5), w_in) for j in range(4)]
    assert svc.compact() == 4  # flush the sealed 4-row segment
    assert svc.delta_summary()["n_rows_compacted"] == 4
    # evict the compacted group by touching every other group
    for other in range(plan.n_groups):
        if other != gi:
            wo = int(plan.groups[other].member_ids[0])
            svc.query(data[1][None].astype(np.float32), [wo])
    assert not svc.state_cache.is_resident(gi)
    res = svc.query(_far_vector(data, 1, 5)[None], [w_in])
    assert res.ids[0][0] == pids[1] and res.dists[0][0] == 0.0


# -------------------------------------------------- post-compaction parity


def _union_host(host: WLSHIndex, union: np.ndarray,
                weights: np.ndarray) -> WLSHIndex:
    """Fresh host index over the union corpus with the same family seeds.

    Eq. 11/12 betas drift with ``z(gamma=gamma_n/n)`` as n grows, so the
    freshly partitioned plan would differ from the served one by a table
    or two; the comparison the streaming stack guarantees is *same plan,
    same family seeds, union corpus* — so the original partition is
    pinned onto the fresh index (families re-sample identically from the
    shared seed) and only the hash tables are rebuilt over the union.
    """
    cfg2 = dataclasses.replace(host.cfg, n=len(union))
    host2 = WLSHIndex(union, weights, cfg2, tau=host.tau,
                      value_range=host.value_range, v=host.v,
                      v_prime=host.v_prime, seed=host.seed)
    host2.part = host.part
    host2._built = {}
    return host2


@pytest.mark.slow_parity
def test_post_compaction_parity_vs_fresh_union_build(parity_setup):
    """Acceptance: insert -> seal -> compact is bit-exact
    (ids/stop/n_checked) with search_dense on a fresh union-corpus index,
    per p in {2, 1, 0.5}, sync + async, paged + unpaged."""
    p, data, weights, host, plan, _ = parity_setup
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    members = plan.groups[gi].member_ids
    m = 24
    rng = np.random.default_rng(71)
    extra = (
        data[rng.choice(len(data), m, replace=False)]
        + rng.normal(0, 3.0, (m, plan.d))
    ).astype(np.float32)
    ins_wids = members[rng.integers(0, len(members), m)]

    svc = _streaming_service(plan, data, reserve=64, seal_rows=8)
    pids = [
        svc.insert(extra[j], int(ins_wids[j])) for j in range(m)
    ]
    assert pids == list(range(plan.n, plan.n + m))
    assert svc.compact() == m
    assert svc.delta_summary()["n_pending"] == 0

    union = np.concatenate([data, extra])
    host2 = _union_host(host, union, weights)

    # mixed queries under the compacted group's member weights: near base
    # points and near the streamed inserts
    nq = 24
    wids = members[rng.integers(0, len(members), nq)]
    qpts = union[rng.choice(len(union), nq, replace=False)].astype(
        np.float32
    )
    qpts += rng.normal(0, 3.0, qpts.shape).astype(np.float32)

    res = svc.query(qpts, wids)
    for qi in range(nq):
        want = host2.search_dense(qpts[qi], weight_id=int(wids[qi]), k=K)
        np.testing.assert_array_equal(
            res.ids[qi], want.ids.astype(np.int32),
            err_msg=f"post-compaction ids mismatch at query {qi} (p={p})",
        )
        assert int(res.stop_levels[qi]) == want.stats.stop_level
        assert int(res.n_checked[qi]) == want.stats.n_checked

    # a service freshly built over the union plan answers identically
    plan2 = host2.export_serving_plan()
    svc_fresh = RetrievalService(
        plan2, union, cfg=ServiceConfig(k=K, q_batch=4)
    )
    res_f = svc_fresh.query(qpts, wids)
    np.testing.assert_array_equal(res.ids, res_f.ids)
    np.testing.assert_array_equal(res.dists, res_f.dists)
    np.testing.assert_array_equal(res.stop_levels, res_f.stop_levels)
    np.testing.assert_array_equal(res.n_checked, res_f.n_checked)

    # paged (cap=1) streaming service, sync chunks + async replay
    paged = _streaming_service(plan, data, cap=1, reserve=64, seal_rows=8)
    for j in range(m):
        paged.insert(extra[j], int(ins_wids[j]))
    paged.compact()
    ids_chunks, stop_chunks, chk_chunks = [], [], []
    for lo in range(0, nq, 4):
        r = paged.query(qpts[lo:lo + 4], wids[lo:lo + 4])
        ids_chunks.append(r.ids)
        stop_chunks.append(r.stop_levels)
        chk_chunks.append(r.n_checked)
    np.testing.assert_array_equal(np.concatenate(ids_chunks), res.ids)
    np.testing.assert_array_equal(np.concatenate(stop_chunks),
                                  res.stop_levels)
    np.testing.assert_array_equal(np.concatenate(chk_chunks),
                                  res.n_checked)

    arrivals = np.cumsum(rng.exponential(1 / 2_000.0, nq))
    asvc = AsyncRetrievalService(paged.batcher, max_delay_ms=2.0,
                                 clock=ManualClock())
    res_a, _ = replay_open_loop(asvc, qpts, wids, arrivals)
    np.testing.assert_array_equal(res_a.ids, res.ids)
    np.testing.assert_array_equal(res_a.stop_levels, res.stop_levels)
    np.testing.assert_array_equal(res_a.n_checked, res.n_checked)


@pytest.mark.slow_parity
def test_compacted_state_bit_equals_fresh_union_state(parity_setup):
    """The compacted device state itself (codes, vectors, n_valid) equals
    a fresh ``build_group_state`` over the union corpus at the same
    capacity — the strongest form of the parity claim."""
    p, data, weights, host, plan, _ = parity_setup
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    w_in = int(plan.groups[gi].member_ids[0])
    m = 12
    rng = np.random.default_rng(5)
    extra = (
        data[rng.choice(len(data), m, replace=False)]
        + rng.normal(0, 3.0, (m, plan.d))
    ).astype(np.float32)
    svc = _streaming_service(plan, data, reserve=32, seal_rows=4)
    for j in range(m):
        svc.insert(extra[j], w_in)
    svc.compact()

    from repro.index.builder import build_group_state, seal_segment

    cfg = svc.group_config(gi)
    sealed_codes = seal_segment(cfg, plan.groups[gi], extra)
    fresh = build_group_state(
        svc.mesh, cfg, data, plan.groups[gi],
        extra_points=extra, extra_codes=sealed_codes,
    )
    got = svc.state_cache.acquire(gi)
    try:
        assert int(got.n_valid) == int(fresh.n_valid) == plan.n + m
        np.testing.assert_array_equal(
            np.asarray(got.codes), np.asarray(fresh.codes)
        )
        np.testing.assert_array_equal(
            np.asarray(got.points, np.float32),
            np.asarray(fresh.points, np.float32),
        )
    finally:
        svc.state_cache.release(gi)


# ------------------------------------------------------------ tombstone purge


def test_purge_drops_tombstones_and_reclaims_capacity(setup):
    """compact(purge=True): tombstoned rows (base and inserted, compacted
    and pending) leave the states, their n_valid capacity is reclaimed,
    the tombstone set is cleared, and no query step recompiles."""
    data, weights, host, plan, _ = setup
    svc = _streaming_service(plan, data, seal_rows=4, reserve=64)
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    w_in = int(plan.groups[gi].member_ids[0])
    pids = [svc.insert(_far_vector(data, j, 21), w_in) for j in range(8)]
    svc.compact()  # absorb them, then tombstone a few
    q = data[11].astype(np.float32)
    victim_base = int(svc.query(q[None], [0]).ids[0][0])
    svc.delete(victim_base)
    svc.delete(pids[2])
    extra = [svc.insert(_far_vector(data, j, 23), w_in) for j in range(3)]
    svc.delete(extra[1])  # a still-pending insert, tombstoned
    n_compiled0 = svc.step_cache.n_compiled
    with svc.state_cache.lease(gi) as st:
        nv_before = int(st.n_valid)

    absorbed = svc.compact(purge=True)
    assert absorbed == 2  # the two surviving pending inserts

    d = svc.delta_summary()
    assert d["n_tombstones"] == 0  # the set is cleared...
    assert d["n_purges"] == 1 and d["n_rows_purged"] >= 3
    assert d["n_base_live"] == plan.n - 1
    assert d["n_pending"] == 0
    assert svc.step_cache.n_compiled == n_compiled0
    with svc.state_cache.lease(gi) as st:
        # 8 compacted - 1 purged + 2 surviving pending - 1 purged base
        assert int(st.n_valid) == nv_before - 1 - 1 + 2
    # ...and deleted rows are *gone*, not filtered: every group rebuilt
    assert svc.cache_summary()["n_invalidations"] >= plan.n_groups
    r = svc.query(q[None], [0])
    assert victim_base not in r.ids[0]
    for j, pid in enumerate(pids):
        r = svc.query(_far_vector(data, j, 21)[None], [w_in])
        if j == 2:
            assert pid not in r.ids[0]
        else:
            assert r.ids[0][0] == pid and r.dists[0][0] == 0.0
    assert svc.query(
        _far_vector(data, 0, 23)[None], [w_in]
    ).ids[0][0] == extra[0]
    assert extra[1] not in svc.query(
        _far_vector(data, 1, 23)[None], [w_in]
    ).ids[0]
    # plan lineage: the purge bumps the version, and the epoch covers
    # every minted id — including the tombstoned pending insert that was
    # dropped instead of absorbed — so a resumed service never reuses one
    assert svc.plan.version >= 2 and svc.plan.corpus_epoch == plan.n + 11
    # a per-group purge is rejected (tombstones are global)
    with pytest.raises(ValueError, match="purge"):
        svc.compact(group=gi, purge=True)


def test_purge_survives_eviction_and_continues_streaming(setup):
    """Post-purge cold rebuilds (discard-mode paging) must reproduce the
    purged corpus — never resurrect dropped rows — and later inserts /
    compactions keep working against the purged base."""
    data, weights, host, plan, _ = setup
    svc = _streaming_service(plan, data, cap=1, offload=False,
                             seal_rows=4, reserve=64)
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    w_in = int(plan.groups[gi].member_ids[0])
    pid = svc.insert(_far_vector(data, 0, 27), w_in)
    q = data[11].astype(np.float32)
    victim_base = int(svc.query(q[None], [0]).ids[0][0])
    svc.delete(victim_base)
    svc.compact(purge=True)
    # page the purged group out by touching every other group
    for other in range(plan.n_groups):
        if other != gi:
            wo = int(plan.groups[other].member_ids[0])
            svc.query(data[1][None].astype(np.float32), [wo])
    assert not svc.state_cache.is_resident(gi)
    r = svc.query(_far_vector(data, 0, 27)[None], [w_in])
    assert r.ids[0][0] == pid and r.dists[0][0] == 0.0
    assert victim_base not in svc.query(q[None], [0]).ids[0]
    # streaming continues on the purged base: insert -> compact -> exact
    pid2 = svc.insert(_far_vector(data, 1, 29), w_in)
    assert svc.compact() == 1
    r = svc.query(_far_vector(data, 1, 29)[None], [w_in])
    assert r.ids[0][0] == pid2 and r.dists[0][0] == 0.0


def test_failed_purge_commits_nothing(setup):
    """The purge is transactional: a capacity overflow raises the same
    explicit delta_reserve_rows error as ordinary compaction *before*
    any state is replaced — tombstones, logs and answers are unchanged."""
    data, weights, host, plan, _ = setup
    svc = _streaming_service(plan, data, seal_rows=2, reserve=4)
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    w_in = int(plan.groups[gi].member_ids[0])
    room = svc.batcher.row_capacity() - plan.n
    pids = [svc.insert(_far_vector(data, j, 31), w_in)
            for j in range(room + 2)]
    svc.delete(0)  # a base tombstone so the purge can't degrade to compact
    with pytest.raises(ValueError, match="delta_reserve_rows"):
        svc.compact(purge=True)
    d = svc.delta_summary()
    assert d["n_purges"] == 0 and d["n_tombstones"] == 1
    assert d["n_base_live"] == plan.n
    assert svc.cache_summary()["n_invalidations"] == 0  # nothing committed
    r = svc.query(_far_vector(data, 2, 31)[None], [w_in])
    assert r.ids[0][0] == pids[2]  # rows keep serving from the exact scan


def test_purge_without_tombstones_degrades_to_compact(setup):
    """With nothing to drop, purge=True must not rebuild every group —
    it delegates to the ordinary append-based full compact."""
    data, weights, host, plan, _ = setup
    svc = _streaming_service(plan, data, seal_rows=2, reserve=16)
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    w_in = int(plan.groups[gi].member_ids[0])
    svc.insert(_far_vector(data, 0, 33), w_in)
    svc.insert(_far_vector(data, 1, 33), w_in)
    assert svc.compact(purge=True) == 2
    d = svc.delta_summary()
    assert d["n_purges"] == 0  # no sweep happened...
    assert d["n_compactions"] == 1  # ...just the ordinary compaction
    assert svc.cache_summary()["n_invalidations"] == 1  # one group touched


def test_identity_purge_rebuilds_only_affected_groups(setup):
    """With the base corpus untouched, a purge rebuilds only groups that
    actually drop a row; everyone else keeps their cached state (sealed
    backlogs take the ordinary append path)."""
    data, weights, host, plan, _ = setup
    svc = _streaming_service(plan, data, seal_rows=4, reserve=64)
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    w_in = int(plan.groups[gi].member_ids[0])
    other = int(np.argmin(
        [g.n_members if g2 != gi else 10**9
         for g2, g in enumerate(plan.groups)]
    ))
    w_other = int(plan.groups[other].member_ids[0])
    pids = [svc.insert(_far_vector(data, j, 41), w_in) for j in range(4)]
    svc.compact(gi)
    pid_other = svc.insert(_far_vector(data, 0, 43), w_other)
    svc.delete(pids[1])  # only group gi drops a row
    inval0 = {g: svc.stats[g].n_state_invalidations
              for g in range(plan.n_groups)}
    svc.compact(purge=True)
    # gi rebuilt (one replace); `other` only absorbed its sealed row
    # (ordinary append compaction); every untouched group: zero churn
    for g in range(plan.n_groups):
        delta = svc.stats[g].n_state_invalidations - inval0[g]
        assert delta == (1 if g in (gi, other) else 0), (g, delta)
    assert svc.delta_summary()["n_tombstones"] == 0
    assert pids[1] not in svc.query(
        _far_vector(data, 1, 41)[None], [w_in]
    ).ids[0]
    assert svc.query(
        _far_vector(data, 0, 43)[None], [w_other]
    ).ids[0][0] == pid_other
    # ...and the optimization survives an earlier base-dropping purge:
    # the next purge compares against the *current* surviving base, so a
    # single-group insert tombstone again touches only that group
    victim_base = int(svc.query(
        data[11][None].astype(np.float32), [0]
    ).ids[0][0])
    svc.delete(victim_base)
    svc.compact(purge=True)  # drops a base row: every group rebuilds
    pid3 = svc.insert(_far_vector(data, 5, 47), w_in)
    svc.compact(gi)
    svc.delete(pid3)
    inval1 = {g: svc.stats[g].n_state_invalidations
              for g in range(plan.n_groups)}
    svc.compact(purge=True)
    for g in range(plan.n_groups):
        delta = svc.stats[g].n_state_invalidations - inval1[g]
        assert delta == (1 if g == gi else 0), (g, delta)


@pytest.mark.slow_parity
def test_purged_state_bit_equals_fresh_surviving_build(parity_setup):
    """Acceptance: the purged state (codes, vectors, n_valid) is bit-exact
    with a fresh ``build_group_state`` over the surviving corpus (live
    base rows + surviving inserts), per p in {2, 1, 0.5}."""
    p, data, weights, host, plan, _ = parity_setup
    gi = int(np.argmax([g.n_members for g in plan.groups]))
    w_in = int(plan.groups[gi].member_ids[0])
    m = 12
    rng = np.random.default_rng(13)
    extra = (
        data[rng.choice(len(data), m, replace=False)]
        + rng.normal(0, 3.0, (m, plan.d))
    ).astype(np.float32)
    svc = _streaming_service(plan, data, reserve=32, seal_rows=4)
    pids = [svc.insert(extra[j], w_in) for j in range(m)]
    svc.compact()
    drop_base = [3, 77]
    drop_ins = [1, 6]
    for b in drop_base:
        svc.delete(b)
    for j in drop_ins:
        svc.delete(pids[j])
    svc.compact(purge=True)

    from repro.index.builder import build_group_state, seal_segment

    cfg = svc.group_config(gi)
    surv_base = np.setdiff1d(
        np.arange(plan.n, dtype=np.int64), drop_base
    )
    keep = [j for j in range(m) if j not in drop_ins]
    surv_vecs = extra[keep]
    sealed_codes = seal_segment(cfg, plan.groups[gi], surv_vecs)
    fresh = build_group_state(
        svc.mesh, cfg, data, plan.groups[gi],
        extra_points=surv_vecs, extra_codes=sealed_codes,
        base_rows=surv_base,
    )
    got = svc.state_cache.acquire(gi)
    try:
        assert int(got.n_valid) == int(fresh.n_valid)
        assert int(got.n_valid) == plan.n - len(drop_base) + len(keep)
        np.testing.assert_array_equal(
            np.asarray(got.codes), np.asarray(fresh.codes)
        )
        np.testing.assert_array_equal(
            np.asarray(got.points, np.float32),
            np.asarray(fresh.points, np.float32),
        )
    finally:
        svc.state_cache.release(gi)
    # surviving rows answer bit-exactly through the compiled path
    for j in keep:
        r = svc.query(extra[j][None], [w_in])
        assert r.ids[0][0] == pids[j] and r.dists[0][0] == 0.0
    for j in drop_ins:
        assert pids[j] not in svc.query(extra[j][None], [w_in]).ids[0]


# --------------------------------------------------------- plan versioning


def test_plan_version_round_trips_npz(tmp_path, setup):
    data, weights, host, plan, _ = setup
    assert plan.version == 0 and plan.corpus_epoch == plan.n
    bumped = plan.bumped(40)
    assert bumped.version == 1 and bumped.corpus_epoch == plan.n + 40
    path = str(tmp_path / "plan_v.npz")
    bumped.save_npz(path)
    loaded = ServingPlan.load_npz(path)
    assert loaded.version == 1
    assert loaded.corpus_epoch == plan.n + 40


def test_compaction_advances_the_served_plan(setup):
    data, weights, host, plan, _ = setup
    svc = _streaming_service(plan, data, seal_rows=4, auto=1)
    w_in = int(plan.groups[0].member_ids[0])
    for j in range(8):
        svc.insert(_far_vector(data, j, 11), w_in)
    assert svc.plan.version == 2  # two auto-compactions
    assert svc.plan.corpus_epoch == plan.n + 8
    # a service resumed from the advanced plan continues the id space
    svc2 = _streaming_service(svc.plan, data)
    pid = svc2.insert(_far_vector(data, 0, 12), w_in)
    assert pid == plan.n + 8


# ------------------------------------------------- hot-path micro-structure


def test_memtable_vectors_cached_no_recopy():
    """The stacked delta matrix is built once per write epoch: repeated
    reads return the *same* array object (no O(m*d) re-stack per scan),
    writes invalidate, and the shared array is read-only."""
    from repro.index.streaming import DeltaSegment

    seg = DeltaSegment(4)
    empty = seg.vectors
    assert empty.shape == (0, 4) and seg.vectors is empty
    seg.append(10, np.arange(4, dtype=np.float32))
    seg.append(11, np.arange(4, dtype=np.float32) + 1)
    v1 = seg.vectors
    assert v1 is seg.vectors  # identity: no copy on the read path
    assert not v1.flags.writeable  # shared across reads, so frozen
    np.testing.assert_array_equal(v1[1], np.arange(4, dtype=np.float32) + 1)
    seg.append(12, np.arange(4, dtype=np.float32) + 2)
    v2 = seg.vectors
    assert v2 is not v1 and v2.shape == (3, 4)  # append invalidates
    ids, vecs = seg.drain()
    assert vecs is v2 and ids.tolist() == [10, 11, 12]
    assert seg.vectors is not v2 and seg.vectors.shape == (0, 4)


def _scan_topk_reference(queries, q_weights, ids, vectors, p, k):
    """The pre-optimization scan_topk: full (Q, m) stable argsort."""
    from repro.index.streaming import exact_weighted_lp

    queries = np.atleast_2d(np.asarray(queries, np.float32))
    nq = len(queries)
    out_ids = np.full((nq, k), -1, np.int64)
    out_d = np.full((nq, k), np.inf, np.float32)
    m = len(ids)
    if m == 0:
        return out_ids, out_d
    dists = exact_weighted_lp(queries, vectors, q_weights, p)
    take = min(k, m)
    order = np.argsort(dists, axis=1, kind="stable")[:, :take]
    out_ids[:, :take] = np.asarray(ids, np.int64)[order]
    out_d[:, :take] = np.take_along_axis(dists, order, axis=1)
    return out_ids, out_d


@pytest.mark.parametrize("p", [2.0, 1.0, 0.5])
@pytest.mark.parametrize("m,k", [(0, 5), (3, 5), (64, 5), (64, 64), (7, 7)])
def test_scan_topk_bit_identical_to_stable_argsort(p, m, k):
    """The argpartition fast path returns bit-identical ids *and* dists
    to the full stable argsort it replaced — including insertion-order
    tie-breaks from duplicated rows (equal distances under every query)."""
    from repro.index.streaming import scan_topk

    rng = np.random.default_rng(97)
    d = 6
    vecs = rng.normal(0, 5, (max(m, 1), d)).astype(np.float32)[:m]
    if m >= 8:
        vecs[5] = vecs[1]  # exact duplicates: distance ties every query
        vecs[7] = vecs[1]
        vecs[6] = vecs[2]
    ids = rng.permutation(10 * max(m, 1))[:m].astype(np.int64)
    q = rng.normal(0, 5, (4, d)).astype(np.float32)
    q[2] = vecs[0] if m else 0.0  # a zero-distance hit
    w = rng.uniform(0.25, 2.0, (4, d)).astype(np.float32)
    got_i, got_d = scan_topk(q, w, ids, vecs, p, k)
    want_i, want_d = _scan_topk_reference(q, w, ids, vecs, p, k)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_array_equal(
        got_d.view(np.uint32), want_d.view(np.uint32)
    )


# ------------------------------------------------------- merge_topk helper


@st.composite
def _merge_case(draw):
    k = draw(st.integers(1, 6))
    na = draw(st.integers(0, 8))
    nb = draw(st.integers(0, 6))
    a_d = sorted(draw(st.lists(
        st.floats(0, 100, allow_nan=False, width=32),
        min_size=na, max_size=na,
    )))
    b_d = sorted(draw(st.lists(
        st.floats(0, 100, allow_nan=False, width=32),
        min_size=nb, max_size=nb,
    )))
    n_drop = draw(st.integers(0, 4))
    return k, a_d, b_d, n_drop


@given(_merge_case())
@settings(max_examples=100, deadline=None)
def test_merge_topk_invariants_property(case):
    """Sorted output, no dropped/duplicated/invented candidate, tombstones
    filtered with backfill, missing slots -1/inf at the tail."""
    k, a_d, b_d, n_drop = case
    ka = max(len(a_d), 1)
    ids_a = np.full((1, ka), -1, np.int64)
    d_a = np.full((1, ka), np.inf, np.float32)
    ids_a[0, :len(a_d)] = np.arange(len(a_d))  # indexed ids 0..
    d_a[0, :len(a_d)] = a_d
    kb = max(len(b_d), 1)
    ids_b = np.full((1, kb), -1, np.int64)
    d_b = np.full((1, kb), np.inf, np.float32)
    ids_b[0, :len(b_d)] = 1_000 + np.arange(len(b_d))  # disjoint delta ids
    d_b[0, :len(b_d)] = b_d
    drop = set(range(0, n_drop)) | {1_000}  # tombstone some of each
    out_ids, out_d = merge_topk(ids_a, d_a, ids_b, d_b, k, drop=drop)
    assert out_ids.shape == (1, k) and out_d.shape == (1, k)
    finite = out_d[0][np.isfinite(out_d[0])]
    assert np.all(np.diff(finite) >= 0)  # sorted ascending
    valid = out_ids[0][out_ids[0] >= 0]
    assert len(set(valid.tolist())) == len(valid)  # no duplicates
    assert not (set(valid.tolist()) & drop)  # tombstones never surface
    # every surfaced id existed in an input with its own distance
    pool = {int(i): float(d) for i, d in zip(ids_a[0], d_a[0]) if i >= 0}
    pool.update(
        {int(i): float(d) for i, d in zip(ids_b[0], d_b[0]) if i >= 0}
    )
    for i, d in zip(out_ids[0], out_d[0]):
        if i >= 0:
            assert pool[int(i)] == pytest.approx(float(d))
    # survivors are exactly the k best non-dropped candidates
    best = sorted(
        (d for i, d in pool.items() if i not in drop)
    )[:k]
    assert list(np.sort(finite)) == pytest.approx(best)


def test_merge_topk_passthrough_is_bit_exact():
    ids = np.array([[4, 9, -1]], np.int32)
    d = np.array([[1.5, 2.5, np.inf]], np.float32)
    empty_i = np.full((1, 0), -1, np.int64)
    empty_d = np.full((1, 0), np.inf, np.float32)
    out_ids, out_d = merge_topk(ids, d, empty_i, empty_d, 3)
    np.testing.assert_array_equal(out_ids, ids)
    np.testing.assert_array_equal(out_d, d)
    # distance ties prefer the indexed operand
    tie_i = np.array([[77]], np.int64)
    tie_d = np.array([[1.5]], np.float32)
    out_ids, _ = merge_topk(ids, d, tie_i, tie_d, 3)
    assert out_ids[0].tolist() == [4, 77, 9]
