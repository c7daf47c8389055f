"""Multi-group serving throughput: queries/s vs active groups & occupancy,
plus the deadline-batching occupancy lift under open-loop traffic.

The paper's experiments measure per-query table-group work; what dominates a
real deployment is the *serving path* — routing a mixed stream across many
weight groups, batch coalescing, and compiled-step reuse.  This benchmark
pins a baseline for that path:

  sweep 1  active groups: the same total query count routed to weights
           drawn from 1, 2, ... all table groups (more groups = more
           device dispatches at fixed work per query)
  sweep 2  batch occupancy: fixed mixed traffic served at submission chunk
           sizes that leave the compiled q_batch increasingly underfilled
           (padding waste on ragged tails)
  sweep 3  deadline batching: the same open-loop Poisson arrival trace
           (each request submitted alone, the worst case of sweep 2)
           served by the async deadline-aware frontend over arrival rate x
           max_delay_ms, vs the sync single-submission baseline — batch
           occupancy bought with bounded queue wait
  sweep 4  group-state paging: the same mixed trace served with the
           StateCache capped at a shrinking resident fraction of the
           plan's groups (1.0 -> 0.25) — throughput and state hit-rate
           vs device-memory budget, answers bit-exact throughout
  sweep 5  streaming writes: the same traffic with a growing fraction of
           ops replaced by streaming inserts (write mix 0 -> 50%) at a
           fixed paging budget — query throughput and p50 latency vs
           insert rate, fresh-insert recall via the exact delta scan,
           then a full compaction absorbs the backlog with zero
           query-step recompiles
  sweep 6  predictive prefetch: the same open-loop trace stepped through
           the real-time ServiceDriver under a tight paging budget (0.5x
           resident fraction), prefetch off vs on — the pending buffers
           are a schedule, so the driver pages states in *ahead* of
           their deadline launches: state hit rate rises, deadline-miss
           rate (deadline expired while the state was off-device) falls,
           answers bit-exact throughout
  sweep 7  sharded group states: the same workload served at n_shards in
           {1, 2, 4, 8} on a forced 8-device CPU mesh (each shard count
           runs in a child process so XLA_FLAGS lands before jax
           initialises).  Row capacity pads to a common block multiple,
           so every shard count runs identical per-block gemms and the
           answers are bit-exact across shard counts — on one
           oversubscribed CPU the throughput column prices the
           collective overhead, not a speedup
  sweep 8  multi-tenant QoS under overload: a 2x-capacity open-loop
           trace split across a strict high-priority tenant (gold,
           weight 4, tight SLO) and a degradable low-priority tenant
           (bronze), stepped on a fixed virtual tick grid with the
           fair queue capped at capacity_per_tick launches — weighted
           fairness must keep gold's SLO-miss rate at ~0 and its
           answers bit-exact strict, while sustained overload steps
           bronze down the pre-compiled (c, k) relaxation ladder
           (degradation on vs off), holding bronze recall above the
           rung's planned bound with zero new compiles
  sweep 9  observability overhead: the sweep-6 driver workload (open-loop
           trace, 0.5x paging budget, prefetch on) served with the obs
           layer off vs fully on (trace spans + profiler over the
           always-on metrics registry) — answers must stay bit-exact and
           the p50 per-launch driver-step time may pay < 5% overhead
  sweep 10 online recall telemetry: the sweep-8 degradation-on overload
           trace replayed with shadow-exact recall sampling off vs on at
           rate 1.0 — every served answer is re-ranked off-path against
           the exact host oracle (driver idle ticks drain the shadow
           queue).  Sampling must not move a single served bit, the
           online micro-averaged estimate must equal an offline oracle
           recomputation on the same sample bit-for-bit, and the
           per-rung observed recall must hold at or above the rung's
           planned bound (strict rung: the configured recall floor)

Validation checks assert the structural claims future PRs must not regress:
compiled steps stay below group count (shape-bucket sharing), full batches
beat 1-query submissions on throughput, the async frontend answers the
trace bit-exactly, deadline batching lifts mean occupancy over
single-submission on every swept configuration, paging stays bit-exact
with live eviction/restore traffic below full residency, prefetch
strictly improves the hit rate and miss rate at the same budget, sharded
serving answers bit-identically at every shard count, turning the
observability layer on neither changes an answer nor costs more than 5%
of the p50 per-launch step time, and shadow-exact recall sampling is
bit-invisible while its online estimate matches the offline oracle
exactly and clears every rung's planned recall bound.

    PYTHONPATH=src python -m benchmarks.run --only serve_bench
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

from repro.core.datagen import make_dataset, make_weight_set
from repro.core.params import PlanConfig
from repro.core.wlsh import WLSHIndex
from repro.serving.async_service import (
    AsyncRetrievalService,
    ManualClock,
    replay_open_loop,
)
from repro.serving.qos import DegradeStep, QosClass, QosScheduler
from repro.serving.retrieval import RetrievalService, ServiceConfig
from repro.serving.scheduler import (
    DeadlinePrefetch,
    ServiceDriver,
    replay_with_driver,
)

from .common import TAU, Timer, print_table, save

K = 5
Q_BATCH = 8


def _build_service(n, d, n_weights, n_subset, seed=0):
    data = make_dataset(n=n, d=d, seed=seed)
    weights = make_weight_set(size=n_weights, d=d, n_subset=n_subset,
                              n_subrange=10, seed=seed + 1)
    cfg = PlanConfig(p=2.0, c=3, n=n, gamma_n=100.0)
    host = WLSHIndex(data, weights, cfg, tau=TAU[2.0], v=d // 4,
                     v_prime=d // 4, seed=seed + 2)
    plan = host.export_serving_plan()
    svc = RetrievalService(
        plan, data,
        cfg=ServiceConfig(k=K, q_batch=Q_BATCH),
    )
    svc.warmup()
    return data, weights, plan, svc


def _traffic(data, weight_ids_pool, n_queries, rng):
    wids = rng.choice(weight_ids_pool, size=n_queries)
    qpts = data[rng.choice(len(data), n_queries, replace=False)].astype(
        np.float32
    )
    qpts += rng.normal(0, 3.0, qpts.shape).astype(np.float32)
    return qpts, wids


def _metrics_condensed(service) -> dict:
    """One-number-per-metric view of a service's registry snapshot.

    Counters and gauges collapse to the sum over their label series;
    histograms report total count and sum.  Small enough to pin a
    per-sweep snapshot into the benchmark payload without drowning it.
    """
    out = {}
    for name, entry in service.batcher.metrics.snapshot().items():
        if entry["type"] == "histogram":
            out[name] = {
                "count": int(sum(s["count"]
                                 for s in entry["series"].values())),
                "sum": float(sum(s["sum"]
                                 for s in entry["series"].values())),
            }
        else:
            total = float(sum(entry["series"].values()))
            out[name] = int(total) if total == int(total) else total
    return out


_SHARD_DEVICES = 8

# Child body for sweep 7.  Each shard count needs its own process:
# XLA_FLAGS must be set before jax initialises, and the parent keeps the
# single real CPU device.  2011 live rows pad (5 reserve rows) to
# 2016 = 32 * 63, so shards in {1, 2, 4, 8} all run (q, 63, d) block
# gemms — the structural precondition for bit-exact answers across
# shard counts (f32 matmuls are shape-sensitive).
_SHARD_CHILD = """
    import json, time
    import numpy as np
    from repro.core.datagen import make_dataset, make_weight_set
    from repro.core.params import PlanConfig
    from repro.core.wlsh import WLSHIndex
    from repro.serving.retrieval import RetrievalService, ServiceConfig

    SHARDS = %(shards)d
    data = make_dataset(n=2011, d=24, seed=7)
    weights = make_weight_set(size=16, d=24, n_subset=8, n_subrange=10,
                              seed=8)
    cfg = PlanConfig(p=2.0, c=3, n=len(data), gamma_n=100.0)
    host = WLSHIndex(data, weights, cfg, tau=500.0, v=6, v_prime=6,
                     seed=9)
    plan = host.export_serving_plan()
    svc = RetrievalService(plan, data, cfg=ServiceConfig(
        k=%(k)d, q_batch=%(q_batch)d, block_n=63, delta_reserve_rows=5,
        n_shards=SHARDS))
    assert svc.mesh.size == SHARDS
    svc.warmup()
    rng = np.random.default_rng(11)
    NQ = %(nq)d
    wids = rng.integers(0, len(weights), NQ)
    qpts = data[rng.choice(len(data), NQ, replace=False)].astype(
        np.float32)
    qpts += rng.normal(0, 3.0, qpts.shape).astype(np.float32)
    svc.query(qpts[:%(q_batch)d], wids[:%(q_batch)d])  # warm dispatch
    svc.reset_stats()
    t0 = time.perf_counter()
    res = svc.query(qpts, wids)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "shards": SHARDS,
        "qps": NQ / dt,
        "rows_per_shard": svc.batcher.row_capacity() // svc.mesh.size,
        "occupancy": float(svc.mean_occupancy()),
        "compiled_steps": svc.step_cache.n_compiled,
        "ids": res.ids.tolist(),
        "n_checked": res.n_checked.tolist(),
    }))
"""


def _shard_child(shards: int, k: int, q_batch: int, nq: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
    )
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_SHARD_DEVICES}"
    )
    env.setdefault("JAX_PLATFORMS", "cpu")
    code = textwrap.dedent(_SHARD_CHILD) % {
        "shards": shards, "k": k, "q_batch": q_batch, "nq": nq,
    }
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=900, env=env,
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"shard child (shards={shards}) failed:\n"
            f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
        )
    return json.loads(r.stdout.strip().splitlines()[-1])


def run(full: bool = False) -> dict:
    n, d = (16_000, 32) if full else (4_096, 24)
    n_weights, n_subset = (48, 12) if full else (16, 8)
    n_queries = 192 if full else 96
    data, weights, plan, svc = _build_service(n, d, n_weights, n_subset)
    rng = np.random.default_rng(3)
    # condensed registry snapshot per sweep, from the service that ran it
    # (sweep 7 runs in child processes and has no registry to read here)
    metrics_by_sweep = {}

    # ---- sweep 1: throughput vs number of active groups ---------------------
    rows_groups = []
    group_members = [g.member_ids for g in plan.groups]
    for n_active in range(1, plan.n_groups + 1):
        pool = np.concatenate(group_members[:n_active])
        qpts, wids = _traffic(data, pool, n_queries, rng)
        svc.query(qpts[:Q_BATCH], wids[:Q_BATCH])  # warm dispatch path
        svc.reset_stats()
        with Timer() as t:
            svc.query(qpts, wids)
        occ = svc.mean_occupancy()
        rows_groups.append([
            n_active, n_queries, n_queries / t.seconds, float(occ),
            svc.step_cache.n_compiled,
        ])
    print_table(
        "serving throughput vs active groups",
        ["groups", "queries", "q/s", "occupancy", "compiled steps"],
        rows_groups,
    )
    metrics_by_sweep["1_active_groups"] = _metrics_condensed(svc)

    # ---- sweep 2: throughput vs batch occupancy -----------------------------
    rows_occ = []
    pool = np.arange(n_weights)
    qpts, wids = _traffic(data, pool, n_queries, rng)
    for chunk in (1, 2, 4, Q_BATCH, n_queries):
        svc.reset_stats()
        with Timer() as t:
            for lo in range(0, n_queries, chunk):
                svc.query(qpts[lo : lo + chunk], wids[lo : lo + chunk])
        occ = svc.mean_occupancy()
        rows_occ.append(
            [chunk, n_queries, n_queries / t.seconds, float(occ)]
        )
    print_table(
        "serving throughput vs submission chunk (batch occupancy)",
        ["chunk", "queries", "q/s", "occupancy"],
        rows_occ,
    )
    metrics_by_sweep["2_occupancy"] = _metrics_condensed(svc)

    # ---- sweep 3: deadline batching vs sync single-submission ---------------
    # one fixed open-loop trace per arrival rate; the sync baseline submits
    # each request alone as it arrives (occupancy 1/q_batch by construction)
    qpts, wids = _traffic(data, pool, n_queries, rng)
    sync_res = svc.query(qpts, wids)
    svc.reset_stats()
    with Timer() as t:
        for qi in range(n_queries):
            svc.query(qpts[qi : qi + 1], wids[qi : qi + 1])
    occ_sync = svc.mean_occupancy()
    qps_sync_single = n_queries / t.seconds
    rows_async = []
    async_exact = True
    for rate in (500.0, 2_000.0, 8_000.0):
        trng = np.random.default_rng(int(rate))
        arrivals = np.cumsum(trng.exponential(1.0 / rate, n_queries))
        for delay_ms in (0.5, 2.0, 10.0):
            asvc = AsyncRetrievalService(svc, max_delay_ms=delay_ms,
                                         clock=ManualClock())
            svc.reset_stats()
            with Timer() as t:
                res, waits = replay_open_loop(asvc, qpts, wids, arrivals)
            async_exact &= bool(
                np.array_equal(res.ids, sync_res.ids)
                and np.array_equal(res.stop_levels, sync_res.stop_levels)
                and np.array_equal(res.n_checked, sync_res.n_checked)
            )
            occ = svc.mean_occupancy()
            rows_async.append([
                rate, delay_ms, occ, occ_sync,
                float(1e3 * waits.mean()),
                float(1e3 * np.percentile(waits, 95)),
                asvc.n_launched_full, asvc.n_launched_deadline,
                n_queries / t.seconds,
            ])
    print_table(
        "async deadline batching vs single-submission "
        f"(sync baseline occupancy {occ_sync:.3f} at {qps_sync_single:.1f} "
        "q/s)",
        ["rate q/s", "deadline ms", "occupancy", "occ sync", "wait ms",
         "p95 wait ms", "full", "deadline", "q/s"],
        rows_async,
    )
    metrics_by_sweep["3_deadline_batching"] = _metrics_condensed(svc)

    # ---- sweep 4: group-state paging under a device-memory budget -----------
    # same mixed trace, submitted in q_batch chunks so group launches
    # interleave (the access pattern that actually exercises LRU paging),
    # with the StateCache capped at a shrinking fraction of the groups
    qpts, wids = _traffic(data, pool, n_queries, rng)
    ref_res = svc.query(qpts, wids)
    rows_paging = []
    paging_exact = True
    for frac in (1.0, 0.75, 0.5, 0.25):
        cap = max(1, int(np.ceil(frac * plan.n_groups)))
        psvc = RetrievalService(
            plan, data,
            cfg=ServiceConfig(k=K, q_batch=Q_BATCH,
                              max_resident_groups=cap),
        )
        psvc.warmup()  # builds every state once; excess groups host-offload
        psvc.reset_stats()
        outs = []
        with Timer() as t:
            for lo in range(0, n_queries, Q_BATCH):
                outs.append(
                    psvc.query(qpts[lo : lo + Q_BATCH],
                               wids[lo : lo + Q_BATCH]).ids
                )
        cs = psvc.state_cache.stats
        paging_exact &= bool(
            np.array_equal(np.concatenate(outs), ref_res.ids)
        )
        rows_paging.append([
            frac, cap, plan.n_groups, n_queries / t.seconds,
            float(cs.hit_rate), cs.n_evictions, cs.n_restores, cs.n_builds,
            psvc.state_cache.resident_bytes,
        ])
    print_table(
        "group-state paging vs resident fraction "
        f"({'bit-exact' if paging_exact else 'MISMATCH'} vs full residency)",
        ["resident frac", "cap", "groups", "q/s", "hit rate", "evictions",
         "restores", "rebuilds", "resident bytes"],
        rows_paging,
    )
    metrics_by_sweep["4_paging"] = _metrics_condensed(psvc)

    # ---- sweep 5: streaming — query throughput / p50 latency vs write mix ---
    # mixed op stream at a fixed paging budget (cap = half the groups);
    # queries go out in stream-order chunks of up to Q_BATCH, inserts land
    # in the delta memtables (seals allowed, compaction deferred so the
    # mid-stream read path is delta-scan + merge); after the stream a full
    # compaction absorbs the backlog and the insert recall is re-checked
    # through the compiled index path
    rows_stream = []
    stream_exact = True
    stream_recall = True
    stream_no_recompile = True
    cap5 = max(1, plan.n_groups // 2)
    qpts, wids = _traffic(data, pool, n_queries, rng)
    base_ref = svc.query(qpts, wids)  # static reference answers
    for mix in (0.0, 0.1, 0.25, 0.5):
        srng = np.random.default_rng(int(mix * 100) + 17)
        ssvc = RetrievalService(
            plan, data,
            cfg=ServiceConfig(k=K, q_batch=Q_BATCH,
                              max_resident_groups=cap5,
                              delta_seal_rows=16,
                              delta_reserve_rows=n_queries),
        )
        ssvc.warmup()
        ssvc.reset_stats()
        n_compiled0 = ssvc.step_cache.n_compiled
        is_ins = srng.random(n_queries) < mix
        ins_vecs = qpts + np.float32(60_000.0) + np.float32(7.0) * (
            np.arange(n_queries, dtype=np.float32)[:, None]
        )
        inserted = []
        got_ids = {}
        lat_s = []
        with Timer() as t:
            i = 0
            while i < n_queries:
                if is_ins[i]:
                    pid = ssvc.insert(ins_vecs[i], int(wids[i]))
                    inserted.append((pid, i))
                    i += 1
                    continue
                lo = i  # stream-order chunk of consecutive reads
                while (i < n_queries and not is_ins[i]
                       and i - lo < Q_BATCH):
                    i += 1
                with Timer() as tq:
                    r = ssvc.query(qpts[lo:i], wids[lo:i])
                lat_s.extend([tq.seconds / (i - lo)] * (i - lo))
                for row, qi in enumerate(range(lo, i)):
                    got_ids[qi] = r.ids[row]
        n_reads = len(lat_s)
        # mid-stream reads bit-exact vs the static reference (inserts are
        # far offsets, so base top-k answers must be untouched)
        for qi, ids in got_ids.items():
            stream_exact &= bool(np.array_equal(ids, base_ref.ids[qi]))
        # fresh-insert recall through the exact delta scan
        for pid, qi in inserted:
            r = ssvc.query(ins_vecs[qi][None], [int(wids[qi])])
            stream_recall &= int(r.ids[0][0]) == pid
        absorbed = ssvc.compact()
        # ... and through the compiled index path after compaction
        for pid, qi in inserted:
            r = ssvc.query(ins_vecs[qi][None], [int(wids[qi])])
            stream_recall &= int(r.ids[0][0]) == pid
        stream_no_recompile &= (
            ssvc.step_cache.n_compiled == n_compiled0
        )
        d = ssvc.delta_summary() or dict(n_seals=0, n_compactions=0)
        rows_stream.append([
            mix, n_reads, len(inserted),
            (n_reads / t.seconds) if n_reads else 0.0,
            1e3 * float(np.percentile(lat_s, 50)) if lat_s else 0.0,
            d["n_seals"], d["n_compactions"], absorbed,
        ])
    print_table(
        "streaming writes: query throughput / p50 latency vs write mix "
        f"(paging cap {cap5}/{plan.n_groups} groups)",
        ["write mix", "reads", "inserts", "read q/s", "p50 read ms",
         "seals", "compactions", "rows compacted"],
        rows_stream,
    )
    metrics_by_sweep["5_streaming"] = _metrics_condensed(ssvc)

    # ---- sweep 6: predictive prefetch under a tight paging budget -----------
    # the same open-loop trace stepped through the real-time ServiceDriver
    # at a 0.5x resident-fraction budget, prefetch off vs on; the driver
    # counts a deadline miss whenever a group's oldest deadline expires
    # while its state is off-device (the restore would serialize into the
    # launch's critical path) — prefetch exists to drive that to zero
    cap6 = max(1, int(np.ceil(0.5 * plan.n_groups)))
    qpts, wids = _traffic(data, pool, n_queries, rng)
    sched_ref = svc.query(qpts, wids)
    srng = np.random.default_rng(29)
    arrivals6 = np.cumsum(srng.exponential(1.0 / 2_000.0, n_queries))
    rows_sched = []
    sched_exact = True
    sched_stats = {}
    for label, policy in (("off", None), ("on", DeadlinePrefetch())):
        dsvc = RetrievalService(
            plan, data,
            cfg=ServiceConfig(k=K, q_batch=Q_BATCH,
                              max_resident_groups=cap6),
        )
        dsvc.warmup()
        dsvc.reset_stats()
        asvc = AsyncRetrievalService(dsvc, max_delay_ms=2.0,
                                     clock=ManualClock())
        driver = ServiceDriver(asvc, prefetch=policy)
        with Timer() as t:
            res, _ = replay_with_driver(driver, qpts, wids, arrivals6)
        sched_exact &= bool(
            np.array_equal(res.ids, sched_ref.ids)
            and np.array_equal(res.stop_levels, sched_ref.stop_levels)
            and np.array_equal(res.n_checked, sched_ref.n_checked)
        )
        cs = dsvc.state_cache.stats
        ds = driver.stats
        sched_stats[label] = (float(cs.hit_rate),
                              float(ds.deadline_miss_rate))
        rows_sched.append([
            label, cap6, plan.n_groups, float(cs.hit_rate),
            float(ds.deadline_miss_rate), ds.n_deadlines_due,
            cs.n_prefetches, cs.n_restore_overlapped, cs.n_prefetch_wasted,
            cs.n_evictions, cs.n_restores, n_queries / t.seconds,
        ])
    print_table(
        "predictive prefetch under a tight paging budget "
        f"(cap {cap6}/{plan.n_groups} groups, "
        f"{'bit-exact' if sched_exact else 'MISMATCH'} vs sync reference)",
        ["prefetch", "cap", "groups", "hit rate", "miss rate", "deadlines",
         "prefetches", "overlapped", "wasted", "evictions", "restores",
         "q/s"],
        rows_sched,
    )
    metrics_by_sweep["6_prefetch"] = _metrics_condensed(dsvc)

    # ---- sweep 7: sharded group states on a forced 8-device CPU mesh --------
    # fixed-size workload regardless of --full: each shard count pays a
    # fresh child-process jax init, and the claim being pinned is
    # bit-exactness + the collective-overhead trend, not absolute q/s
    rows_shard = []
    shard_exact = True
    shard_base = None
    for shards in (1, 2, 4, 8):
        out = _shard_child(shards, k=K, q_batch=Q_BATCH, nq=n_queries)
        if shard_base is None:
            shard_base = out
        shard_exact &= bool(
            out["ids"] == shard_base["ids"]
            and out["n_checked"] == shard_base["n_checked"]
        )
        rows_shard.append([
            shards, out["rows_per_shard"], out["qps"],
            out["occupancy"], out["compiled_steps"],
        ])
    print_table(
        "sharded serving vs shard count "
        f"({_SHARD_DEVICES}-device forced CPU mesh, "
        f"{'bit-exact' if shard_exact else 'MISMATCH'} across counts)",
        ["shards", "rows/shard", "q/s", "occupancy", "compiled steps"],
        rows_shard,
    )

    # ---- sweep 8: multi-tenant QoS under 2x-capacity overload ---------------
    # fixed virtual tick grid (a wall-clock driver's cadence): the fair
    # queue may spend capacity_per_tick launch-cost units per tick, so
    # the service ceiling is q_batch * capacity / tick_s queries/s and
    # the trace arrives at 2x that.  Gold (weight 4, strict) is sized
    # within its fair share; bronze (weight 1, degradable) supplies the
    # overload.  Every launch flows through the weighted-fair queue
    # (submit defers full buffers to the tick under QoS), so deferral
    # pressure is sustained and the hysteresis steps bronze down the
    # pre-compiled ladder.
    ladder8 = (DegradeStep(c=4, k=3, cost=0.5, recall_bound=0.3),)
    tick8, cap8 = 0.005, 2.0
    rate8 = 2.0 * Q_BATCH * cap8 / tick8  # 2x the tick-capacity ceiling
    n8 = 4 * n_queries
    qrng = np.random.default_rng(37)
    qpts8, wids8 = _traffic(data, pool, n8, qrng)
    ref8 = svc.query(qpts8, wids8)  # strict oracle answers
    arr8 = np.cumsum(qrng.exponential(1.0 / rate8, n8))
    ten8 = [str(t) for t in
            qrng.choice(["gold", "bronze"], n8, p=[0.25, 0.75])]
    rows_qos = []
    qos_results = {}
    for label, degradable in (("off", False), ("on", True)):
        qsvc = RetrievalService(plan, data, cfg=ServiceConfig(
            k=K, q_batch=Q_BATCH,
            degrade_ladder=ladder8))
        qsvc.warmup()  # compiles every rung's step ahead of traffic
        qsvc.reset_stats()
        n_compiled8 = qsvc.step_cache.n_compiled
        qos = QosScheduler(
            classes=[QosClass("gold", weight=4.0, slo_ms=25.0),
                     QosClass("bronze", weight=1.0, slo_ms=60.0,
                              degradable=degradable)],
            ladder=ladder8, capacity_per_tick=cap8,
            degrade_after=3, restore_after=3,
        )
        asvc = AsyncRetrievalService(qsvc, clock=ManualClock(), qos=qos)
        driver = ServiceDriver(asvc, prefetch=None)
        futs = [None] * n8
        i8, t8 = 0, 0.0
        with Timer() as t:
            while i8 < n8 or asvc.pending_count:
                while i8 < n8 and arr8[i8] <= t8:
                    asvc.clock.advance_to(arr8[i8])
                    futs[i8] = asvc.submit(qpts8[i8], wids8[i8],
                                           tenant=ten8[i8])
                    i8 += 1
                asvc.clock.advance_to(t8)
                driver.step()
                # next tick: the grid cadence, pulled earlier when a
                # pending deadline falls inside the interval — a punctual
                # launch then fires exactly at its deadline (as the
                # event-driven replays do) instead of being counted
                # missed by up to one tick of grid quantization.  Under
                # backlog, deferred deadlines are already past, so
                # draining still happens at the capacity-per-grid-tick
                # rate.
                nxt = t8 + tick8
                nd = asvc.next_deadline()
                if nd is not None and t8 < nd < nxt:
                    nxt = nd
                t8 = nxt
                assert driver.stats.n_ticks < 100_000, "sweep 8 stalled"
        recall8 = {"gold": [], "bronze": []}
        exact8 = {"gold": True, "bronze": True}
        for qi in range(n8):
            ids = futs[qi].result().ids
            want = ref8.ids[qi]
            valid = set(int(x) for x in want if x >= 0)
            got = set(int(x) for x in ids if x >= 0)
            recall8[ten8[qi]].append(
                len(got & valid) / max(1, len(valid))
            )
            exact8[ten8[qi]] &= bool(np.array_equal(ids, want))
        s8 = qos.summary()
        qos_results[label] = dict(
            summary=s8, exact=exact8,
            recall={k: float(np.mean(v)) for k, v in recall8.items()},
            new_compiles=qsvc.step_cache.n_compiled - n_compiled8,
        )
        rows_qos.append([
            label,
            s8["tenants"]["gold"]["slo_miss_rate"],
            s8["tenants"]["bronze"]["slo_miss_rate"],
            qos_results[label]["recall"]["gold"],
            qos_results[label]["recall"]["bronze"],
            s8["tenants"]["bronze"]["n_degraded"],
            s8["n_degrade_steps"],
            1e3 * s8["tenants"]["gold"]["mean_wait_s"],
            1e3 * s8["tenants"]["bronze"]["mean_wait_s"],
            qos_results[label]["new_compiles"],
        ])
    print_table(
        "multi-tenant QoS at 2x-capacity overload, degradation off vs on "
        f"(gold strict weight 4, bronze degradable weight 1; ladder "
        f"c={ladder8[0].c} k={ladder8[0].k} cost={ladder8[0].cost})",
        ["degrade", "gold miss", "bronze miss", "gold recall",
         "bronze recall", "n degraded", "ladder steps", "gold wait ms",
         "bronze wait ms", "new compiles"],
        rows_qos,
    )
    metrics_by_sweep["8_qos"] = _metrics_condensed(qsvc)

    # ---- sweep 9: observability overhead at the sweep-6 settings ------------
    # the sweep-6 driver workload (same trace, same 0.5x paging budget,
    # prefetch on) with the obs layer off vs fully on: per-query trace
    # spans + per-signature profiler attribution over the always-on
    # metrics registry.  Each driver.step() is wall-timed; the p50 is
    # taken over the steps that launched a batch (arrival-only steps do
    # no compiled work) and the reported step time is the median over
    # OBS_REPS fresh-service runs per setting.  Spans mark stages on the
    # virtual ManualClock but the *recording* cost lands on the wall
    # steps being timed, which is exactly the overhead being priced.
    OBS_REPS = 3

    def _obs_run(obs_on: bool) -> dict:
        osvc = RetrievalService(
            plan, data,
            cfg=ServiceConfig(k=K, q_batch=Q_BATCH,
                              max_resident_groups=cap6, obs=obs_on),
        )
        osvc.warmup()
        osvc.reset_stats()
        oasvc = AsyncRetrievalService(osvc, max_delay_ms=2.0,
                                      clock=ManualClock())
        odriver = ServiceDriver(oasvc, prefetch=DeadlinePrefetch())
        launch_times = []
        seen = [0]
        real_step = odriver.step

        def timed_step():
            t0 = time.perf_counter()
            out = real_step()
            dt = time.perf_counter() - t0
            n = odriver.stats.n_launches
            if n > seen[0]:
                launch_times.append(dt)
                seen[0] = n
            return out

        odriver.step = timed_step
        res, _ = replay_with_driver(odriver, qpts, wids, arrivals6)
        tr = osvc.batcher.tracer
        return {
            "res": res,
            "p50_step_s": float(np.percentile(launch_times, 50)),
            "n_launches": odriver.stats.n_launches,
            "spans": (None if tr is None
                      else (tr.n_started, tr.n_finished)),
            "svc": osvc,
        }

    obs_runs = {"off": [], "on": []}
    for _rep in range(OBS_REPS):
        for label in ("off", "on"):
            obs_runs[label].append(_obs_run(label == "on"))
    obs_exact = all(
        bool(np.array_equal(r_on["res"].ids, r_off["res"].ids)
             and np.array_equal(r_on["res"].stop_levels,
                                r_off["res"].stop_levels)
             and np.array_equal(r_on["res"].n_checked,
                                r_off["res"].n_checked))
        for r_off, r_on in zip(obs_runs["off"], obs_runs["on"])
    ) and bool(
        np.array_equal(obs_runs["off"][0]["res"].ids, sched_ref.ids)
    )
    obs_spans_exact = all(
        r["spans"] == (n_queries, n_queries) for r in obs_runs["on"]
    )
    obs_p50 = {
        label: float(np.median([r["p50_step_s"] for r in runs]))
        for label, runs in obs_runs.items()
    }
    obs_overhead = obs_p50["on"] / obs_p50["off"] - 1.0
    rows_obs = [
        [label, cap6, obs_runs[label][0]["n_launches"],
         1e3 * obs_p50[label],
         (0.0 if label == "off" else obs_overhead)]
        for label in ("off", "on")
    ]
    print_table(
        "observability overhead at the sweep-6 settings "
        f"({'bit-exact' if obs_exact else 'MISMATCH'}, p50 per-launch "
        f"step time over median of {OBS_REPS} runs)",
        ["obs", "cap", "launches", "p50 step ms", "overhead"],
        rows_obs,
    )
    metrics_by_sweep["9_obs_overhead"] = _metrics_condensed(
        obs_runs["on"][-1]["svc"]
    )

    # ---- sweep 10: online recall telemetry on the sweep-8 overload trace ----
    # the degradation-on QoS replay rerun with shadow-exact recall
    # sampling off vs on at rate 1.0: a deterministic hash of the query
    # id picks the sample (here: everything), served answers are queued
    # as host-copy shadow jobs, and the driver's idle ticks re-rank them
    # against the exact oracle off the serving path.  recall_floor pins
    # the strict rung's bound; the degraded rung carries the ladder's
    # planned recall_bound.
    RECALL_FLOOR = 0.5

    def _recall_replay(sample_rate: float):
        rsvc = RetrievalService(plan, data, cfg=ServiceConfig(
            k=K, q_batch=Q_BATCH,
            degrade_ladder=ladder8,
            recall_sample_rate=sample_rate,
            recall_floor=RECALL_FLOOR))
        rsvc.warmup()
        rsvc.reset_stats()
        qos = QosScheduler(
            classes=[QosClass("gold", weight=4.0, slo_ms=25.0),
                     QosClass("bronze", weight=1.0, slo_ms=60.0,
                              degradable=True)],
            ladder=ladder8, capacity_per_tick=cap8,
            degrade_after=3, restore_after=3,
        )
        asvc = AsyncRetrievalService(rsvc, clock=ManualClock(), qos=qos)
        driver = ServiceDriver(asvc, prefetch=None)
        futs = [None] * n8
        i10, t10 = 0, 0.0
        while i10 < n8 or asvc.pending_count:
            while i10 < n8 and arr8[i10] <= t10:
                asvc.clock.advance_to(arr8[i10])
                futs[i10] = asvc.submit(qpts8[i10], wids8[i10],
                                        tenant=ten8[i10])
                i10 += 1
            asvc.clock.advance_to(t10)
            driver.step()
            nxt = t10 + tick8
            nd = asvc.next_deadline()
            if nd is not None and t10 < nd < nxt:
                nxt = nd
            t10 = nxt
            assert driver.stats.n_ticks < 100_000, "sweep 10 stalled"
        return rsvc, futs

    ref_svc, ref_futs = _recall_replay(0.0)
    rec_svc, rec_futs = _recall_replay(1.0)
    est = rec_svc.batcher.recall
    n_drained_idle = est.summary()["n_executed"]  # driver idle ticks
    est.drain()
    recall_exact = all(
        bool(np.array_equal(rec_futs[qi].result().ids,
                            ref_futs[qi].result().ids)
             and rec_futs[qi].result().n_checked
             == ref_futs[qi].result().n_checked)
        for qi in range(n8)
    )
    # offline oracle recomputation on the same sample: the estimator's
    # own exact scan per query, folded with the same integer counts
    off_hits = off_rel = 0
    for qi in range(n8):
        r = rec_futs[qi].result()
        exact = est.oracle_topk(qpts8[qi], int(wids8[qi]),
                                int(r.group_id))
        exact_set = {int(i) for i in exact if i >= 0}
        served_set = {int(i) for i in np.asarray(r.ids).reshape(-1)
                      if i >= 0}
        off_hits += len(served_set & exact_set)
        off_rel += len(exact_set)
    online_est = est.estimate()
    offline_est = off_hits / off_rel if off_rel else float("nan")
    rsum = est.summary()
    rows_recall = [
        [rung, rsum["observed"][rung], rsum["bound"][rung],
         rsum["observed"][rung] - rsum["bound"][rung]]
        for rung in sorted(rsum["observed"], key=int)
    ]
    print_table(
        "online recall telemetry on the sweep-8 overload trace "
        f"({'bit-exact' if recall_exact else 'MISMATCH'} vs sampling "
        f"off; {rsum['n_executed']} shadow checks, {n_drained_idle} "
        f"drained on idle ticks; online {online_est:.4f} vs offline "
        f"{offline_est:.4f})",
        ["rung", "observed recall", "planned bound", "margin"],
        rows_recall,
    )
    metrics_by_sweep["10_recall"] = _metrics_condensed(rec_svc)

    qps_full = rows_occ[-1][2]
    qps_single = rows_occ[0][2]
    occ_async_min = min(r[2] for r in rows_async)
    occ_async_max = max(r[2] for r in rows_async)
    validation = [
        {
            "check": "compiled steps < table groups (shape-bucket sharing)",
            "ok": bool(svc.step_cache.n_compiled < plan.n_groups),
        },
        {
            "check": "full-batch submission beats 1-query submission",
            "ok": bool(qps_full > qps_single),
        },
        {
            "check": "mean occupancy > 0.45 when traffic arrives in one batch",
            "ok": bool(rows_occ[-1][3] > 0.45),
        },
        {
            "check": "async frontend bit-exact with sync on the same trace",
            "ok": async_exact,
        },
        {
            "check": "deadline batching lifts occupancy over "
                     "single-submission on every (rate, deadline)",
            "ok": bool(occ_async_min > occ_sync),
        },
        {
            "check": "occupancy at the largest rate x deadline >= 2x "
                     "single-submission",
            "ok": bool(occ_async_max >= 2 * occ_sync),
        },
        {
            "check": "paging bit-exact vs full residency at every "
                     "resident fraction",
            "ok": paging_exact,
        },
        {
            "check": "full residency serves with hit rate 1.0 after warmup",
            "ok": bool(rows_paging[0][4] == 1.0),
        },
        {
            "check": "capped residency pages live (evictions and restores "
                     "> 0 at the smallest fraction)",
            "ok": bool(rows_paging[-1][5] > 0 and rows_paging[-1][6] > 0),
        },
        {
            "check": "state hit rate decreases as the resident fraction "
                     "shrinks",
            "ok": bool(rows_paging[-1][4] < rows_paging[0][4]),
        },
        {
            "check": "mixed-stream reads bit-exact with the static "
                     "reference at every write mix",
            "ok": stream_exact,
        },
        {
            "check": "fresh inserts recalled exactly, pre- and "
                     "post-compaction, at every write mix",
            "ok": stream_recall,
        },
        {
            "check": "streaming (seal + compact) never recompiles a "
                     "query step",
            "ok": stream_no_recompile,
        },
        {
            "check": "the 50% write mix seals and compacts a real backlog",
            "ok": bool(rows_stream[-1][5] > 0 and rows_stream[-1][7] > 0),
        },
        {
            "check": "driver-stepped replay bit-exact with the sync "
                     "reference, prefetch on and off, at the 0.5x budget",
            "ok": sched_exact,
        },
        {
            "check": "prefetch strictly lifts the state hit rate at the "
                     "same paging budget",
            "ok": bool(sched_stats["on"][0] > sched_stats["off"][0]),
        },
        {
            "check": "prefetch strictly lowers the deadline-miss rate "
                     "(and prefetch-off actually misses)",
            "ok": bool(sched_stats["off"][1] > sched_stats["on"][1]),
        },
        {
            "check": "prefetch-on serves every deadline with its state "
                     "already on device (miss rate 0)",
            "ok": bool(sched_stats["on"][1] == 0.0),
        },
        {
            "check": "sharded answers (ids, n_checked) bit-exact across "
                     "shard counts {1, 2, 4, 8} on the forced 8-device "
                     "mesh",
            "ok": shard_exact,
        },
        {
            "check": "each shard holds exactly capacity / n_shards rows "
                     "(strict placement, no replication)",
            "ok": bool(all(
                r[0] * r[1] == rows_shard[0][1] for r in rows_shard
            )),
        },
        {
            "check": "qos: weighted fairness keeps the strict gold "
                     "tenant's SLO-miss rate ~0 under 2x-capacity "
                     "overload (degradation on)",
            "ok": bool(
                qos_results["on"]["summary"]["tenants"]["gold"]
                ["slo_miss_rate"] <= 0.02
            ),
        },
        {
            "check": "qos: gold answers stay bit-exact strict under "
                     "overload (a degraded step never touches a "
                     "non-degradable tenant)",
            "ok": bool(qos_results["on"]["exact"]["gold"]),
        },
        {
            "check": "qos: sustained overload steps bronze down the "
                     "ladder (degrade transitions and degraded answers "
                     "> 0)",
            "ok": bool(
                qos_results["on"]["summary"]["n_degrade_steps"] > 0
                and qos_results["on"]["summary"]["tenants"]["bronze"]
                ["n_degraded"] > 0
            ),
        },
        {
            "check": "qos: degraded bronze recall stays above the "
                     "rung's planned relaxation bound",
            "ok": bool(
                qos_results["on"]["recall"]["bronze"]
                >= ladder8[0].recall_bound
            ),
        },
        {
            "check": "qos: degradation relieves bronze (mean wait "
                     "strictly below the degradation-off run)",
            "ok": bool(
                qos_results["on"]["summary"]["tenants"]["bronze"]
                ["mean_wait_s"]
                < qos_results["off"]["summary"]["tenants"]["bronze"]
                ["mean_wait_s"]
            ),
        },
        {
            "check": "qos: degraded steps compile nothing new (rungs "
                     "pre-compiled at warmup), on and off",
            "ok": bool(
                qos_results["on"]["new_compiles"] == 0
                and qos_results["off"]["new_compiles"] == 0
            ),
        },
        {
            "check": "obs: tracing + profiling on is bit-exact (ids, "
                     "stop levels, n_checked) vs obs off on the sweep-6 "
                     "workload",
            "ok": obs_exact,
        },
        {
            "check": "obs: every submitted query yields exactly one "
                     "finished trace span on every obs-on run",
            "ok": obs_spans_exact,
        },
        {
            "check": "obs: p50 per-launch step-time overhead below 5% "
                     "with the full obs layer on",
            "ok": bool(obs_overhead < 0.05),
        },
        {
            "check": "recall: shadow sampling at rate 1.0 is bit-exact "
                     "(ids, n_checked) vs sampling off on the overload "
                     "trace",
            "ok": recall_exact,
        },
        {
            "check": "recall: the online micro-averaged estimate equals "
                     "the offline oracle recomputation bit-for-bit",
            "ok": bool(online_est == offline_est),
        },
        {
            "check": "recall: every sampled query was shadow-checked "
                     "(no drops, full coverage at rate 1.0)",
            "ok": bool(rsum["n_executed"] == n8
                       and rsum["n_dropped"] == 0),
        },
        {
            "check": "recall: the driver's idle ticks drained shadow "
                     "work off-path during the replay",
            "ok": bool(n_drained_idle > 0),
        },
        {
            "check": "recall: per-rung observed recall holds at or "
                     "above the rung's planned bound",
            "ok": bool(all(r[1] >= r[2] for r in rows_recall)),
        },
    ]
    for v in validation:
        print(("PASS " if v["ok"] else "FAIL ") + v["check"])

    payload = {
        "n": n, "d": d, "n_weights": n_weights,
        "n_groups": plan.n_groups,
        "n_compiled_steps": svc.step_cache.n_compiled,
        "groups_sweep": rows_groups,
        "occupancy_sweep": rows_occ,
        "async_sweep": rows_async,
        "async_sweep_columns": [
            "arrival_rate_qps", "max_delay_ms", "occupancy",
            "occupancy_sync_single", "mean_wait_ms", "p95_wait_ms",
            "n_launched_full", "n_launched_deadline", "qps_compute",
        ],
        "occupancy_sync_single": occ_sync,
        "qps_sync_single": qps_sync_single,
        "paging_sweep": rows_paging,
        "paging_sweep_columns": [
            "resident_fraction", "max_resident_groups", "n_groups",
            "qps", "state_hit_rate", "n_evictions", "n_restores",
            "n_rebuilds", "resident_bytes",
        ],
        "streaming_sweep": rows_stream,
        "streaming_sweep_columns": [
            "write_mix", "n_reads", "n_inserts", "read_qps",
            "p50_read_latency_ms", "n_seals", "n_compactions",
            "n_rows_compacted",
        ],
        "streaming_paging_cap": cap5,
        "scheduler_sweep": rows_sched,
        "scheduler_sweep_columns": [
            "prefetch", "max_resident_groups", "n_groups",
            "state_hit_rate", "deadline_miss_rate", "n_deadlines_due",
            "n_prefetches", "n_restore_overlapped", "n_prefetch_wasted",
            "n_evictions", "n_restores", "qps",
        ],
        "scheduler_paging_cap": cap6,
        "sharding_sweep": rows_shard,
        "sharding_sweep_columns": [
            "n_shards", "rows_per_shard", "qps", "occupancy",
            "n_compiled_steps",
        ],
        "sharding_forced_devices": _SHARD_DEVICES,
        "qos_sweep": rows_qos,
        "qos_sweep_columns": [
            "degradation", "gold_slo_miss_rate", "bronze_slo_miss_rate",
            "gold_recall", "bronze_recall", "bronze_n_degraded",
            "n_degrade_steps", "gold_mean_wait_ms", "bronze_mean_wait_ms",
            "n_new_compiles",
        ],
        "qos_ladder": [dataclasses.asdict(s) for s in ladder8],
        "qos_capacity_per_tick": cap8,
        "qos_tick_s": tick8,
        "qos_overload_rate_qps": rate8,
        "obs_sweep": rows_obs,
        "obs_sweep_columns": [
            "obs", "max_resident_groups", "n_launches",
            "p50_launch_step_ms", "p50_overhead_fraction",
        ],
        "obs_overhead_fraction": float(obs_overhead),
        "obs_reps": OBS_REPS,
        "recall_sweep": rows_recall,
        "recall_sweep_columns": [
            "rung", "observed_recall", "planned_bound", "margin",
        ],
        "recall_online_estimate": float(online_est),
        "recall_offline_estimate": float(offline_est),
        "recall_n_shadow_checks": int(rsum["n_executed"]),
        "recall_n_drained_idle": int(n_drained_idle),
        "recall_floor": RECALL_FLOOR,
        "metrics_by_sweep": metrics_by_sweep,
        "validation": validation,
    }
    save("serve_bench", payload)
    return payload


if __name__ == "__main__":
    run()
