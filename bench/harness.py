"""One run of one cell: set-up, window, reference check, metrics, result.

``run_cell`` returns the result object that ``bench/run.py`` prints as
the last line of standard output, and writes the compared numbers beside
their limits as the last lines of standard error.  The reference runs
after the window has closed, the peak device memory has been read and
the program's state has been freed.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import time

import jax
import numpy as np

from . import gen, serve, spec, trace, work
from .reference import Answers, compare, reference_answers

__all__ = ["NoAccelerator", "RunRecord", "percentile", "run_cell"]


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of raw samples (linear interpolation).

    A request that was never answered counts as infinitely late.
    """
    v = np.asarray(values, np.float64)
    v = np.sort(np.where(np.isfinite(v), v, np.inf))
    pos = (len(v) - 1) * q / 100.0
    lo = int(np.floor(pos))
    frac = pos - lo
    if frac == 0.0:
        return float(v[lo])
    if not np.isfinite(v[lo + 1]):
        return float("inf")
    return float(v[lo] + (v[lo + 1] - v[lo]) * frac)


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read about one run."""

    seconds: float
    setup_s: float
    plan_s: float
    build_s: float
    window: serve.WindowRecord
    q_batch: int
    peak_bytes: int
    counters: dict  # program counters over the window
    dispatch: dict | None  # Profiler dispatch seconds/launches, window
    spans: list[dict] | None  # per request: submit, launch, group, beta
    trace: dict | None  # trace.reduce_events of the traced part
    scan_bytes_traced: int | None  # work.scan_bytes over traced launches
    peaks: dict | None


def _devices(chips: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs


def _peak_bytes(devs) -> int:
    stats = [d.memory_stats() or {} for d in devs]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def _counters(svc) -> dict:
    s = svc.stats_summary()
    return {"n_queries": sum(g["n_queries"] for g in s.values()),
            "n_batches": sum(g["n_batches"] for g in s.values())}


def _dispatch(svc) -> tuple[float, int]:
    prof = svc.batcher.profiler
    if prof is None:
        return 0.0, 0
    rows = prof.summary()["dispatch"].values()
    return (sum(r["total_s"] for r in rows), sum(r["count"] for r in rows))


def _spans(svc, dep: serve.Deployment, t_open: float) -> list[dict]:
    tr = svc.batcher.tracer
    out = []
    for s in tr.spans():
        st = s.stages
        if (st.get("submit", -np.inf) < t_open or "launch" not in st
                or "merge" not in st):
            continue
        out.append({"submit": st["submit"], "launch": st["launch"],
                    "merge": st["merge"], "group": s.group_id,
                    "beta": dep.defn.member[s.weight_id][1]})
    return out


def _traced_work(spans: list[dict], dep: serve.Deployment,
                 span: tuple[float, float], q_batch: int):
    """Bytes and operations of the launches wholly inside the traced span."""
    launches: dict[tuple[int, float], int] = {}
    for s in spans:
        if span[0] <= s["launch"] and s["merge"] <= span[1]:
            key = (s["group"], s["launch"])
            launches[key] = max(launches.get(key, 0), s["beta"])
    d = dep.config["d"]
    nbytes = ops = 0
    for (g, _), beta in launches.items():
        shape = dep.group_shapes[g]
        nbytes += work.scan_bytes(shape["rows"], beta, d)
        ops += work.scan_ops(q_batch, shape["rows"], shape["beta"],
                             shape["n_levels"], d)
    return nbytes, ops, len(launches)


def _served(window: serve.WindowRecord, idx: np.ndarray, k: int) -> Answers:
    ans = [window.answers[i] for i in idx]
    return Answers(
        ids=np.stack([a.ids for a in ans]).astype(np.int64).reshape(-1, k),
        dists=np.stack([a.dists for a in ans]).astype(np.float64).reshape(
            -1, k),
        stop=np.array([a.stop_level for a in ans], np.int64),
        n_checked=np.array([a.n_checked for a in ans], np.int64))


def _checks(limits: dict, unanswered: int, compiles: int,
            plan_mismatches: int, numbers: dict) -> dict:
    """Each number that decides ``correct``, beside its limit."""
    values = dict(unanswered=unanswered, compiles_in_window=compiles,
                  plan_mismatches=plan_mismatches, **numbers)
    return {name: {"value": v, "limit": limits.get(name, 0)}
            for name, v in values.items()}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace_on: bool, *,
             t_start: float, require_tpu: bool = True,
             with_control: bool = False) -> dict:
    """One run of ``cell`` (see the module docstring); returns the result.

    ``with_control`` also answers the sample with the control, in the
    program's place, and reports under ``control`` its checks and whether
    they hold (the control script's use only).
    """
    devs = _devices(cell.chips, require_tpu)
    cfg = cell.config
    dep = serve.build(cfg, seed, obs=trace_on)
    seq_traffic, seq_sample = gen.seeds(seed)[2:]
    schedule = gen.make_schedule(cell.traffic, seconds, dep.data,
                                 cfg["n_weights"], seq_traffic)
    svc = dep.svc
    d0 = _dispatch(svc)
    trace_at = None
    if trace_on:
        a = min(cfg["trace_start_s"], 0.4 * seconds)
        trace_at = (a, min(a + cfg["trace_seconds"], seconds))
    setup_s = time.perf_counter() - t_start
    win = serve.run_window(svc, schedule, seconds, trace_at=trace_at)
    peak = _peak_bytes(devs[:cell.chips])
    counters = _counters(svc)
    d1 = _dispatch(svc)
    spans = _spans(svc, dep, win.t_open) if trace_on else None
    traced = scan = None
    if trace_on:
        traced = trace.reduce_events(trace.load_xplane(win.trace_dir))
        shutil.rmtree(win.trace_dir, ignore_errors=True)
        scan, ops, n_launch = _traced_work(spans, dep, win.trace_span,
                                           cfg["q_batch"])
        print(f"traced launches={n_launch} scan_bytes={scan} "
              f"scan_ops={ops} busy_s={traced['busy_s']}", file=sys.stderr)
    rec = RunRecord(
        seconds=seconds, setup_s=setup_s, plan_s=dep.plan_s,
        build_s=dep.build_s, window=win, q_batch=cfg["q_batch"],
        peak_bytes=peak, counters=counters,
        dispatch=({"seconds": d1[0] - d0[0], "launches": d1[1] - d0[1]}
                  if trace_on else None),
        spans=spans, trace=traced, scan_bytes_traced=scan,
        peaks=spec.load_peaks(devs[0].device_kind) if trace_on else None)
    metrics = {}
    for m in (cell.per_layer if trace_on else cell.end_to_end):
        value = spec.metric_reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the check: free the program's state, then run the reference
    dep.svc = svc = None
    gc.collect()
    answered = np.flatnonzero(np.isfinite(win.t_resolved))
    rng = np.random.default_rng(seq_sample)
    idx = np.sort(rng.choice(answered, min(len(answered),
                                           cfg["check_sample"]),
                             replace=False))
    qs, wids = schedule.queries[idx], schedule.weight_ids[idx]
    served = _served(win, idx, cfg["k"])
    answerers = [served]
    if with_control:
        answerers.append(reference_answers(
            dep.data, dep.weights, qs, wids, dep.defn, cfg["k"],
            control=True)[0])
    failed = win.n_failed_submits + win.n_unanswered
    if len(dep.defn.families) == len(cfg["plan"]["groups"]):
        ref, ref_dists = reference_answers(
            dep.data, dep.weights, qs, wids, dep.defn, cfg["k"],
            check_ids=[a.ids for a in answerers])
        numbers = [compare(a, ref, d, cfg["tie_rel"])
                   for a, d in zip(answerers, ref_dists)]
    else:  # the run planned other groups: its answers cannot be checked
        numbers = [{"mismatch_share": 1.0, "dist_rel_err_max": 1e300}
                   for _ in answerers]
    checks = _checks(cfg["limits"], failed, win.n_compiles,
                     dep.plan_mismatches, numbers[0])
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(schedule),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs),
                   "memory_peak_bytes": peak},
    }
    if trace_on:
        result["device"].update(busy_s=traced["busy_s"],
                                window_s=traced["window_s"])
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    if with_control:
        ctrl = _checks(cfg["limits"], failed, win.n_compiles,
                       dep.plan_mismatches, numbers[1])
        result["control"] = {
            "correct": all(c["value"] <= c["limit"] for c in ctrl.values()),
            "checks": ctrl}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    return result
