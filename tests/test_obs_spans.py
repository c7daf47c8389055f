"""The served path's profiler spans, captured by ``jax.profiler`` on the CPU.

Pinned claims:

* a real-time window through the async frontend and the driver thread,
  with the obs layer on, writes the seven ``wlsh_*`` span kinds into a
  ``jax.profiler`` capture, as the benchmark's ``bench.trace.load_xplane``
  reads it: ``wlsh_readback`` nested in ``wlsh_query_step``, and the
  driver's waits never overlapping a launch stage on its thread;
* with the obs layer off the same window writes no ``wlsh_*`` event,
  and a launch or a driver tick builds no annotation at all.
"""

from __future__ import annotations

import pathlib
import sys
import time

import jax
import numpy as np
import pytest

from conftest import build_parity_service
from repro.serving import (
    AsyncRetrievalService,
    RetrievalService,
    ServiceConfig,
    ServiceDriver,
)

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

SPANS = ("wlsh_wait_idle", "wlsh_wait_deadline", "wlsh_lease",
         "wlsh_encode", "wlsh_query_step", "wlsh_readback", "wlsh_resolve")
LAUNCH = ("wlsh_lease", "wlsh_encode", "wlsh_query_step", "wlsh_readback",
          "wlsh_resolve")


def _service(obs: bool) -> RetrievalService:
    _, data, _, _, plan, _ = build_parity_service(2.0)
    svc = RetrievalService(plan, data, cfg=ServiceConfig(
        k=5, q_batch=4, max_delay_ms=10.0, obs=obs))
    svc.warmup()
    for gi, g in enumerate(plan.groups):  # compile outside the window
        svc.batcher.run_batch(gi, data[:1], np.array([g.member_ids[0]]))
    return svc


def _window(svc: RetrievalService, n: int = 12) -> None:
    """``n`` requests, one every 15 ms, through the driver thread."""
    _, data, weights, _, _, _ = build_parity_service(2.0)
    rng = np.random.default_rng(7)
    queries = data[rng.choice(len(data), n, replace=False)]
    wids = rng.integers(0, len(weights), n)
    driver = ServiceDriver(AsyncRetrievalService(svc, clock=time.monotonic))
    driver.start()
    try:
        time.sleep(0.02)  # the driver idles on its tick first
        futures = []
        for q, w in zip(queries, wids):
            futures.append(driver.submit(q, int(w)))
            time.sleep(0.015)
        limit = time.monotonic() + 10.0
        while (not all(f.done() for f in futures)
               and time.monotonic() < limit):
            time.sleep(0.002)
    finally:
        driver.stop(drain=True)
    assert all(f.done() for f in futures)


def _captured(obs: bool, tmp_path) -> dict:
    svc = _service(obs)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _window(svc)
    finally:
        jax.profiler.stop_trace()
    return trace.load_xplane(str(tmp_path))


def _driver_line(tmp_path) -> list[tuple[str, int, int]]:
    """(label, start, end) of the ``wlsh_*`` events on the waits' thread."""
    from jax.profiler import ProfileData

    (path,) = tmp_path.glob("**/*.xplane.pb")
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs = [(trace._label(ev.name), int(ev.start_ns),
                    int(ev.start_ns + ev.duration_ns))
                   for ev in line.events if ev.name.startswith("wlsh_")]
            if any(lab.startswith("wlsh_wait_") for lab, _, _ in evs):
                return evs
    raise AssertionError("no thread holds a wlsh_wait_* span")


def test_a_served_window_writes_the_seven_spans(tmp_path):
    events = _captured(True, tmp_path)
    host = [(trace._label(n), s, s + d) for n, s, d in events["host"]]
    assert {lab for lab, _, _ in host} == set(SPANS)
    steps = [(s, e) for lab, s, e in host if lab == "wlsh_query_step"]
    for lab, s, e in host:
        if lab == "wlsh_readback":
            assert any(a <= s and e <= b for a, b in steps)
    line = _driver_line(tmp_path)
    waits = [(s, e) for lab, s, e in line if lab.startswith("wlsh_wait_")]
    stages = [(s, e) for lab, s, e in line if lab in LAUNCH]
    assert stages, "the driver thread launched nothing"
    for a, b in waits:
        assert all(e <= a or b <= s for s, e in stages)


def test_obs_off_writes_no_program_span(tmp_path):
    events = _captured(False, tmp_path)
    assert not [n for n, _, _ in events["host"] if n.startswith("wlsh_")]


@pytest.mark.parametrize("obs", [False, True])
def test_a_launch_and_a_tick_build_annotations_only_with_obs(
        obs, monkeypatch):
    svc = _service(obs)
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    _window(svc, n=4)
    _, data, _, _, plan, _ = build_parity_service(2.0)
    svc.batcher.run_batch(0, data[:2],
                          np.repeat(plan.groups[0].member_ids[:1], 2))
    if obs:
        assert {trace._label(n) for n in built} >= set(LAUNCH)
    else:
        assert built == []
