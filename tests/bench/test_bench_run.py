"""Whole benchmark runs at a tiny size on the CPU, sound and broken.

The harness's look for a chip is skipped; everything else runs as on the
chip: plan, build, the real-time window through the async frontend and
the driver thread, then the reference check.  A sound run is correct; a
run whose served path is broken underneath, or the control in the
program's place, is not.
"""

from __future__ import annotations

import copy
import os
import pathlib
import subprocess
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, pins, spec  # noqa: E402

TINY = dict(n=2000, d=16, v=4, check_sample=96, trace_start_s=0.5,
            trace_seconds=0.5)


def _tiny_cell(name: str) -> spec.Cell:
    cell = spec.load_cell(name)
    cfg = dict(copy.deepcopy(cell.config), **TINY)
    cfg["plan"] = pins.plan_pins(cfg)
    return spec.Cell(name=cell.name, chips=1, config=cfg,
                     traffic=dict(cell.traffic, rate_qps=60.0),
                     end_to_end=cell.end_to_end, per_layer=cell.per_layer)


def _run(cell, seed=2**31 + 11, trace_on=False, with_control=False):
    return harness.run_cell(cell, seed, 1.5, trace_on,
                            t_start=time.perf_counter(), require_tpu=False,
                            with_control=with_control)


def test_a_sound_run_is_correct_and_reports_its_metrics():
    res = _run(_tiny_cell("sift128_p2.steady"))
    assert res["correct"] is True
    assert res["attempted"] == 90 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "latency_p50_ms",
                                   "latency_p95_ms", "peak_hbm_gib"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert res["checks"]["plan_mismatches"]["value"] == 0
    assert res["checks"]["mismatch_share"]["value"] == 0.0


def _broken(monkeypatch, fault: str):
    from repro.serving import batching

    real = batching.Batcher.run_batch

    def run_batch(self, gi, queries, weight_ids, **kw):
        ids, dists, stop, chk = real(self, gi, queries, weight_ids, **kw)
        ids, dists, stop = ids.copy(), dists.copy(), stop.copy()
        if fault == "answer":
            ids[:, 0] = (ids[:, 0] + 1) % len(self.points)
        elif fault == "stop_level":
            stop += 1
        elif fault == "distance":
            dists *= 1.01
        return ids, dists, stop, chk

    monkeypatch.setattr(batching.Batcher, "run_batch", run_batch)


@pytest.mark.parametrize("fault", ["answer", "stop_level", "distance"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        monkeypatch, fault):
    _broken(monkeypatch, fault)
    res = _run(_tiny_cell("sift128_p2.steady"))
    assert res["correct"] is False


def test_an_answer_that_never_comes_is_not_correct(monkeypatch):
    from repro.serving import async_service

    real = async_service.QueryFuture._resolve
    seen = []

    def resolve(self, answer, now):
        seen.append(1)
        if len(seen) % 17:  # every 17th future is lost
            real(self, answer, now)

    monkeypatch.setattr(async_service.QueryFuture, "_resolve", resolve)
    res = _run(_tiny_cell("sift128_p2.steady"))
    assert res["failed"] > 0
    assert res["correct"] is False


def test_a_planner_that_departs_from_the_pinned_plan_is_not_correct(
        monkeypatch):
    from repro.core import wlsh

    real = wlsh.WLSHIndex._effective_mus

    def raised(self, plan):  # a higher collision threshold for everyone
        return real(self, plan) + 1

    monkeypatch.setattr(wlsh.WLSHIndex, "_effective_mus", raised)
    res = _run(_tiny_cell("sift128_p2.steady"))
    assert res["checks"]["plan_mismatches"]["value"] == 8
    assert res["correct"] is False


@pytest.mark.parametrize("config", ["sift128_p2", "gist960_p1"])
def test_the_control_fails_the_configurations_limits(config):
    res = _run(_tiny_cell(f"{config}.steady"), with_control=True)
    assert res["correct"] is True
    ctrl = res["control"]
    assert ctrl["correct"] is False, ctrl
    assert set(ctrl["checks"]) == set(res["checks"])


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "sift128_p2.steady", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
