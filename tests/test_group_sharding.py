"""Sharded big-group serving: strict placement + multi-device parity.

Host-side tests cover the strict sharding-rule contract (the
``spec(strict=True)`` raise, the warn-once replication fallback, range
math, per-shard byte pricing).  Everything needing a populated mesh runs
in a child process under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the main process must keep the single real CPU device), mirroring
tests/test_multidevice.py.

The parity suite pins the acceptance claim: sharded search is bit-exact
(ids, dists, stop, n_checked) with the single-device engine for
p in {2, 1, 0.5}, sync + async, paged + unpaged, including a ragged
(non-divisible) live row count.  Bit-exactness across shard counts
requires identical per-block gemm shapes (f32 matmuls are
shape-sensitive), so the fixtures pin ``block_n`` and pad the row
capacity to a common multiple via ``delta_reserve_rows`` — the same
masked-capacity machinery streaming uses.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

from repro.distributed import group_sharding
from repro.distributed.sharding import spec
from repro.index.config import IndexConfig

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


class _FakeMesh:
    """Duck-typed mesh for host-side spec() tests (no devices needed)."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


# ------------------------------------------------------- strict placement


def test_spec_strict_raises_on_non_dividing_dim():
    mesh = _FakeMesh(data=8, model=1)
    with pytest.raises(ValueError, match="strict sharding refuses"):
        spec(mesh, ("rows", None), (1003, 16), strict=True)
    # a dividing shape passes strict and shards over the present axes
    p = spec(mesh, ("rows", None), (1008, 16), strict=True)
    assert p == spec(mesh, ("rows", None), (1008, 16))


def test_spec_replication_fallback_warns_once_per_shape():
    mesh = _FakeMesh(data=8, model=1)
    shape = (1001, 3)  # unique shape so the warn-once set can't be primed
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        p1 = spec(mesh, ("rows", None), shape)
        p2 = spec(mesh, ("rows", None), shape)
    assert p1 == p2  # replicated fallback, same answer both calls
    msgs = [str(x.message) for x in w if x.category is UserWarning]
    assert len(msgs) == 1, msgs  # once per (name, shape), not per call
    assert "replicating" in msgs[0] and "8x" in msgs[0]


def test_serving_mesh_validates_device_count():
    with pytest.raises(ValueError, match="n_shards must be >= 1"):
        group_sharding.serving_mesh(0)
    import jax

    too_many = jax.device_count() + 1
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        group_sharding.serving_mesh(too_many)
    mesh = group_sharding.serving_mesh(1)
    assert mesh.axis_names == ("data", "model") and mesh.size == 1


@pytest.mark.parametrize("axis", ["data", "model"])
def test_serving_mesh_axes_are_auto(axis):
    """The serving mesh leaves sharding to the compiler (Auto axes):
    under ``jax.make_mesh``'s Explicit default, compaction's in-place row
    update of a resident state is refused."""
    from jax.sharding import AxisType

    mesh = group_sharding.serving_mesh(1, axis=axis)
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_host_row_ranges_cover_capacity_evenly():
    assert group_sharding.host_row_ranges(1008, 8) == [
        (s * 126, (s + 1) * 126) for s in range(8)
    ]
    assert group_sharding.host_row_ranges(64, 1) == [(0, 64)]
    with pytest.raises(ValueError, match="does not divide"):
        group_sharding.host_row_ranges(1003, 8)


def test_state_nbytes_prices_the_per_device_slice():
    one = IndexConfig(n=1 << 20, d=32, beta=64, n_shards=1)
    eight = IndexConfig(n=1 << 20, d=32, beta=64, n_shards=8)
    # family (proj + b_int/b_frac + width) + n_valid stay replicated;
    # the row arrays (codes i32 + bf16 vectors) scale 1/8 per device
    family_and_scalars = 32 * 64 * 4 + 64 * (4 + 4) + 4 + 4
    rows_one = one.state_nbytes - family_and_scalars
    rows_eight = eight.state_nbytes - family_and_scalars
    assert rows_one == (1 << 20) * (64 * 4 + 32 * 2)
    assert rows_eight == rows_one // 8
    # shard count is compile-relevant: distinct compiled-step cache keys
    assert one.shape_signature() != eight.shape_signature()
    assert one != eight
    assert np.isfinite(rows_eight)  # sanity: accounting stays integral


# ------------------------------------------------- multi-device parity


_PARITY_SETUP = """
    import numpy as np, jax
    from repro.core.datagen import make_dataset, make_weight_set
    from repro.core.params import PlanConfig
    from repro.core.wlsh import WLSHIndex
    from repro.serving import (AsyncRetrievalService, ManualClock,
                               RetrievalService, ServiceConfig,
                               replay_open_loop)

    assert jax.device_count() == 8
    P_VAL = %(p)s
    # 1003 live rows: ragged under every shard count > 1.  The capacity
    # is whole 256-row tiles per shard (1024 rows for 1 and 2 shards,
    # 2048 for 8), and block_n=63 divides every shard's slice down to
    # 32-row blocks, so every shard count runs identical (q, 32, d) block
    # gemms and bit-exactness is structural, not luck (f32 matmuls are
    # shape-sensitive).
    data = make_dataset(n=1003, d=16, seed=41)
    weights = make_weight_set(size=8, d=16, n_subset=4, n_subrange=10,
                              seed=42)
    pcfg = PlanConfig(p=P_VAL, c=3, n=len(data), gamma_n=100.0)
    host = WLSHIndex(data, weights, pcfg, tau=500.0, v=4, v_prime=4,
                     seed=9)
    plan = host.export_serving_plan()
    rng = np.random.default_rng(43)
    NQ = 12
    wids = rng.integers(0, len(weights), NQ)
    qpts = data[rng.choice(len(data), NQ, replace=False)].astype(np.float32)
    qpts += rng.normal(0, 3.0, qpts.shape).astype(np.float32)

    def svc_for(shards, **kw):
        svc = RetrievalService(plan, data, cfg=ServiceConfig(
            k=3, q_batch=4, block_n=63, delta_reserve_rows=5,
            n_shards=shards, **kw))
        assert svc.mesh.size == shards
        return svc

    def assert_same(a, b, what):
        np.testing.assert_array_equal(a.ids, b.ids, err_msg=what)
        np.testing.assert_array_equal(
            a.dists.view(np.uint32), b.dists.view(np.uint32), err_msg=what)
        np.testing.assert_array_equal(a.stop_levels, b.stop_levels,
                                      err_msg=what)
        np.testing.assert_array_equal(a.n_checked, b.n_checked,
                                      err_msg=what)
"""


@pytest.mark.slow_parity
@pytest.mark.parametrize("p", [2.0, 1.0, 0.5])
def test_sharded_search_bit_exact_with_unsharded(p):
    """Acceptance: shards in {2, 8} answer bit-identically (ids, dists,
    stop, n_checked) to the single-device engine — sync, async, paged —
    on a ragged (1003-row) corpus, per p."""
    out = _run(_PARITY_SETUP % {"p": p} + """
    base = svc_for(1).query(qpts, wids)
    # the unsharded answers agree with the host oracle, so the sharded
    # ones transitively do too
    for qi in range(NQ):
        want = host.search_dense(qpts[qi], weight_id=int(wids[qi]), k=3)
        np.testing.assert_array_equal(base.ids[qi],
                                      want.ids.astype(np.int32))
        assert int(base.stop_levels[qi]) == want.stats.stop_level
        assert int(base.n_checked[qi]) == want.stats.n_checked
    for shards in (2, 8):
        svc = svc_for(shards)
        assert_same(svc.query(qpts, wids), base, f"sync shards={shards}")
        # paged: one resident group, sharded offload/restore per shard
        paged = svc_for(shards, max_resident_groups=1)
        chunks = [paged.query(qpts[lo:lo + 4], wids[lo:lo + 4])
                  for lo in range(0, NQ, 4)]
        np.testing.assert_array_equal(
            np.concatenate([c.ids for c in chunks]), base.ids,
            err_msg=f"paged shards={shards}")
        np.testing.assert_array_equal(
            np.concatenate([c.n_checked for c in chunks]), base.n_checked)
        # async open-loop replay over the sharded paged service
        arrivals = np.cumsum(rng.exponential(1 / 2000.0, NQ))
        asvc = AsyncRetrievalService(paged.batcher, max_delay_ms=2.0,
                                     clock=ManualClock())
        res_a, _ = replay_open_loop(asvc, qpts, wids, arrivals)
        assert_same(res_a, base, f"async shards={shards}")
    print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow_parity
def test_sharded_offload_restore_roundtrip_per_shard():
    """Evicting a sharded state keeps one host chunk per shard (replicas
    deduped) and restoring it round-trips the exact device bytes."""
    out = _run(_PARITY_SETUP % {"p": 2.0} + """
    from repro.distributed.group_sharding import (
        offload_state_sharded, restore_state_sharded)

    svc = svc_for(8)
    svc.warmup()
    gi = int(svc.batcher.route(wids)[0])
    with svc.state_cache.lease(gi) as st:
        before_codes = np.asarray(st.codes)
        before_pts = np.asarray(st.points, np.float32)
        host = offload_state_sharded(st)
    # 1003 rows in whole 256-row tiles on each of 8 shards
    cap = svc.batcher.row_capacity()
    assert cap == 8 * 256
    assert len(host.codes) == 8 and len(host.points) == 8
    assert all(c.shape[1] == cap // 8 for c in host.codes)
    np.testing.assert_array_equal(np.concatenate(host.codes, axis=1),
                                  before_codes)
    restored = restore_state_sharded(svc.mesh, host)
    np.testing.assert_array_equal(np.asarray(restored.codes), before_codes)
    np.testing.assert_array_equal(
        np.asarray(restored.points, np.float32), before_pts)
    assert int(restored.n_valid) == 1003
    # the restored placement is the strict row sharding (8 distinct rows
    # slices, nothing replicated)
    starts = {s.index[1].start or 0 for s in restored.codes.addressable_shards}
    assert len(starts) == 8
    print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow_parity
def test_per_host_build_matches_materialized_build():
    """``build_group_state(points_loader=...)`` is bit-exact with the
    materialized-corpus build at the same capacity, and the loader only
    ever sees per-shard row ranges — never the whole corpus."""
    out = _run(_PARITY_SETUP % {"p": 2.0} + """
    from repro.index.builder import build_group_state

    svc = svc_for(8)
    gi = int(svc.batcher.route(wids)[0])
    cfg = svc.group_config(gi)
    gplan = plan.groups[gi]
    whole = build_group_state(svc.mesh, cfg, data, gplan)

    calls = []
    def loader(lo, hi):
        calls.append((lo, hi))
        return data[lo:hi]

    hosted = build_group_state(svc.mesh, cfg, None, gplan,
                               points_loader=loader, n_points=len(data))
    # one call per shard range that holds live rows (dead ranges skipped)
    n_loc = svc.batcher.row_capacity() // 8
    assert len(calls) == -(-len(data) // n_loc), calls
    assert all(hi - lo <= n_loc for lo, hi in calls), calls
    np.testing.assert_array_equal(np.asarray(hosted.codes),
                                  np.asarray(whole.codes))
    np.testing.assert_array_equal(np.asarray(hosted.points, np.float32),
                                  np.asarray(whole.points, np.float32))
    assert int(hosted.n_valid) == int(whole.n_valid) == len(data)

    # misuse is rejected explicitly
    try:
        build_group_state(svc.mesh, cfg, data, gplan,
                          points_loader=loader, n_points=len(data))
        raise AssertionError("points + points_loader must be rejected")
    except ValueError:
        pass
    print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow_parity
def test_strict_sharding_refuses_non_dividing_capacity_on_mesh():
    """A row capacity that does not divide an 8-device mesh raises the
    strict-mode error at step construction — never a silent 8x replica."""
    out = _run("""
    import jax
    from repro.index.config import IndexConfig
    from repro.index.engine import make_query_step

    assert jax.device_count() == 8
    mesh = jax.make_mesh((8, 1), ("data", "model"))
    cfg = IndexConfig(n=1003, d=16, beta=32, q_batch=4, k=3, block_n=59,
                      vec_dtype="float32")
    try:
        make_query_step(mesh, cfg)
        raise AssertionError("non-dividing capacity must raise")
    except ValueError as e:
        assert "strict sharding refuses" in str(e), e
    print("OK")
    """)
    assert "OK" in out
