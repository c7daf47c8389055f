"""Set-up of one deployment and one open-loop window on the served path.

Set-up: corpus from the seed, weight set from the configuration, the
host planner (``WLSHIndex(...).export_serving_plan()``), the device
states and compiled steps (``RetrievalService.warmup()``), and one launch
per group through ``Batcher.run_batch`` so that the window compiles
nothing.

Window: ``AsyncRetrievalService`` on ``time.monotonic`` under a started
``ServiceDriver`` thread; the calling thread is the load generator, which
sleeps until each request's due time and calls ``driver.submit``.  A
request's latency runs from its due time until its future resolves,
which happens after ``run_batch`` has read the outputs back to the host.
"""

from __future__ import annotations

import dataclasses
import math
import sys
import tempfile
import threading
import time
import traceback

import jax
import numpy as np

from . import gen, pins
from .reference import IndexDefinition

__all__ = ["Deployment", "WindowRecord", "build", "run_window"]

# the event JAX records for every executable it builds or loads
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Deployment:
    """A built deployment: inputs, plan, warmed service and timings."""

    config: dict
    data: np.ndarray
    weights: np.ndarray
    defn: IndexDefinition
    svc: object  # repro.serving.RetrievalService
    group_shapes: list[dict]  # per group: beta, n_levels, rows (scan work)
    plan_mismatches: int  # pinned plan values the run's plan departs from
    plan_s: float
    build_s: float


def _definition(config: dict, weights: np.ndarray, plan) -> IndexDefinition:
    """The index as the configuration pins it, with the run's hash draws.

    Group centers, bucket widths and every weight's group, beta, mu,
    r_min and level count come from the configuration's ``plan``; only the
    sampled projections and offsets, which the seed draws, come from the
    run's plan.  The hash functions weight a point by the center's weight
    vector held in float32, as the configuration's precision states.
    """
    pinned = config["plan"]
    families = tuple(
        dict(proj=np.array(g.proj), b_int=np.array(g.b_int),
             b_frac=np.array(g.b_frac), width=pg["width"],
             center_weight=weights[pg["center_id"]].astype(np.float32))
        for g, pg in zip(plan.groups, pinned["groups"]))
    member = {wid: (m["group"], m["beta"], m["mu"], m["r_min"],
                    m["n_levels"])
              for wid, m in enumerate(pinned["members"])}
    return IndexDefinition(
        p=float(config["p"]), c=int(config["c"]),
        budget=config["k"] + int(math.ceil(config["gamma_n"])),
        families=families, member=member)


def build(config: dict, seed: int, *, obs: bool) -> Deployment:
    """Set-up of ``config`` for ``seed`` (see the module docstring)."""
    from repro.core.params import PlanConfig
    from repro.core.wlsh import WLSHIndex
    from repro.serving import RetrievalService, ServiceConfig

    seq_data, seq_hash, _, _ = gen.seeds(seed)
    data = gen.make_dataset(config["n"], config["d"], config["value_range"],
                            seq_data)
    weights = gen.make_weight_set(config["n_weights"], config["d"],
                                  config["n_subset"], config["n_subrange"],
                                  seed=config["weights_seed"])
    t0 = time.perf_counter()
    host = WLSHIndex(
        data, weights,
        PlanConfig(p=config["p"], c=config["c"], n=config["n"],
                   gamma_n=config["gamma_n"]),
        tau=config["tau"], v=config["v"], v_prime=config["v"],
        value_range=config["value_range"],
        seed=int(seq_hash.generate_state(1, np.uint32)[0]))
    plan = host.export_serving_plan()
    del host
    plan_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    svc = RetrievalService(plan, data, cfg=ServiceConfig(
        k=config["k"], q_batch=config["q_batch"],
        max_delay_ms=config["max_delay_ms"], obs=obs,
        obs_trace_capacity=1 << 20))
    svc.warmup()
    for gi, g in enumerate(plan.groups):
        svc.batcher.run_batch(gi, data[:1], np.array([g.member_ids[0]]))
    svc.reset_stats()
    build_s = time.perf_counter() - t0
    shapes = [dict(beta=int(g.beta_group),
                   beta_members=[int(b) for b in g.beta_members],
                   n_levels=svc.group_config(gi).n_levels,
                   rows=svc.batcher.row_capacity())
              for gi, g in enumerate(plan.groups)]
    return Deployment(config=config, data=data, weights=weights,
                      defn=_definition(config, weights, plan), svc=svc,
                      group_shapes=shapes,
                      plan_mismatches=pins.plan_mismatches(config["plan"],
                                                           plan),
                      plan_s=plan_s, build_s=build_s)


@dataclasses.dataclass
class WindowRecord:
    """What one open-loop window did, on the service's monotonic clock."""

    seconds: float
    t_open: float
    due: np.ndarray  # (N,) absolute due times
    t_submit: np.ndarray  # (N,) when submit was called (nan = never)
    t_resolved: np.ndarray  # (N,) when the future resolved (nan = never)
    answers: list  # (N,) QueryAnswer or None
    n_failed_submits: int
    n_compiles: int  # executables built or loaded while the window ran
    trace_span: tuple[float, float] | None  # traced part of the window
    trace_dir: str | None

    @property
    def latency_ms(self) -> np.ndarray:
        """Due time to resolution, ms, for every request (nan = never)."""
        return 1e3 * (self.t_resolved - self.due)

    @property
    def gen_late_ms(self) -> np.ndarray:
        """How late the generator called submit, ms."""
        return 1e3 * (self.t_submit - self.due)

    @property
    def n_unanswered(self) -> int:
        """Requests due in the window that never got an answer."""
        return int(np.sum(~np.isfinite(self.t_resolved)))


class _CompileCounter:
    """Counts executables JAX builds or loads while installed."""

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self)


def _annotated(fn, name: str):
    def wrapper(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapper


def _profile(trace_dir: str, start: float, stop: float,
             span: list) -> None:
    """Profiler thread: trace [start, stop] of the monotonic clock."""
    time.sleep(max(0.0, start - time.monotonic()))
    jax.profiler.start_trace(trace_dir)
    try:
        t_on = time.monotonic()
        with jax.profiler.TraceAnnotation("bench_trace_window"):
            time.sleep(max(0.0, stop - time.monotonic()))
        span.extend([t_on, time.monotonic()])
    finally:
        jax.profiler.stop_trace()


def run_window(svc, schedule: gen.Schedule, seconds: float, *,
               trace_at: tuple[float, float] | None = None,
               drain_timeout_s: float = 120.0) -> WindowRecord:
    """Drive ``schedule`` through the real-time served path.

    ``trace_at`` = (start, stop) seconds after the window opens profiles
    that part of it, with the benchmark's host spans around submits and
    driver ticks; the profile lands in a temporary directory.
    """
    from repro.serving.async_service import AsyncRetrievalService
    from repro.serving.scheduler import ServiceDriver

    asvc = AsyncRetrievalService(svc, clock=time.monotonic)
    driver = ServiceDriver(asvc)
    submit = driver.submit
    if trace_at is not None:
        driver.step = _annotated(driver.step, "bench_driver_tick")
        submit = _annotated(driver.submit, "bench_submit")
    n = len(schedule)
    t_submit = np.full(n, np.nan)
    futures: list = [None] * n
    n_failed = 0
    trace_dir = span = prof = None
    with _CompileCounter() as compiles:
        driver.start()
        t_open = time.monotonic() + 0.05
        due = t_open + schedule.due_s
        if trace_at is not None:
            trace_dir, span = tempfile.mkdtemp(prefix="bench_trace_"), []
            prof = threading.Thread(
                target=_profile, name="bench-profiler",
                args=(trace_dir, t_open + trace_at[0],
                      t_open + trace_at[1], span))
            prof.start()
        try:
            for i in range(n):
                wait = due[i] - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                t_submit[i] = time.monotonic()
                try:
                    futures[i] = submit(schedule.queries[i],
                                        int(schedule.weight_ids[i]))
                except Exception:
                    n_failed += 1
                    traceback.print_exc(file=sys.stderr)
            # let the driver launch what is left on its deadlines; once no
            # buffer holds a request, stopping it (which waits for its
            # current tick) leaves every answer that will come resolved
            limit = time.monotonic() + drain_timeout_s
            while (driver.running and time.monotonic() < limit
                   and asvc.pending_count):
                time.sleep(0.002)
        finally:
            try:
                driver.stop(drain=True)
            except Exception:  # a failed drain leaves requests unanswered
                traceback.print_exc(file=sys.stderr)
            if prof is not None:
                prof.join()
    t_res = np.array([f.t_resolved if f is not None and f.done() else np.nan
                      for f in futures], dtype=np.float64)
    answers = [f.result() if f is not None and f.done() else None
               for f in futures]
    return WindowRecord(
        seconds=seconds, t_open=t_open, due=due, t_submit=t_submit,
        t_resolved=t_res, answers=answers, n_failed_submits=n_failed,
        n_compiles=compiles.n,
        trace_span=tuple(span) if span else None, trace_dir=trace_dir)
