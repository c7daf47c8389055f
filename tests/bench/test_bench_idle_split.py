"""The split of the device's idle time by the program's spans, on the CPU.

The three readers of idle time by cause (deadline wait, a launch's host
work, time no span covers) on a hand-built trace with known answers and
on a trace recorded on the chip with the program's spans.
"""

from __future__ import annotations

import gzip
import json
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec, trace  # noqa: E402

READERS = ("idle_deadline_share.steady", "idle_launch_host_share.steady",
           "idle_unattributed_share.steady")


def _read(name: str, reduced: dict | None):
    return spec.metric_reader(name)(types.SimpleNamespace(trace=reduced))


def _synthetic() -> dict:
    """A 120 ms window, one gap under each label (see the comments)."""
    ms = 1_000_000
    busy = [(10, 20), (30, 40), (44, 45), (50, 58), (60, 61), (70, 80),
            (85, 90), (100, 105), (110, 115)]
    return {
        "devices": {"/device:TPU:0": [
            [f"fusion.{i}", a * ms, (b - a) * ms]
            for i, (a, b) in enumerate(busy)]},
        "host": [[name, int(a * ms), int((b - a) * ms)] for name, a, b in [
            ("bench_trace_window", 0, 120),
            ("wlsh_wait_idle", 0, 12),  # gap [0, 10)
            ("wlsh_wait_deadline", 20, 30),  # gap [20, 30)
            ("bench_driver_tick", 38, 100),  # gap [90, 100)
            ("wlsh_lease[3]", 40, 46),  # gap [40, 44)
            ("wlsh_encode[2]", 46, 50),  # gap [45, 50)
            ("wlsh_query_step[sig]", 50, 80),  # gap [58, 60)
            ("wlsh_readback", 62, 79),  # gap [61, 70)
            ("wlsh_resolve[2]", 80, 86),  # gap [80, 85)
            # gap [105, 110): no span
            ("bench_submit", 116, 119),  # gap [115, 120)
        ]],
    }


def test_the_idle_split_gives_known_answers():
    r = trace.reduce_events(_synthetic())
    assert r["busy_s"] == pytest.approx(0.055)
    assert dict(r["idle_gaps"]) == pytest.approx({
        "wlsh_wait_idle": 0.010, "wlsh_wait_deadline": 0.010,
        "wlsh_lease": 0.004, "wlsh_encode": 0.005,
        "wlsh_query_step": 0.002, "wlsh_readback": 0.009,
        "wlsh_resolve": 0.005, "bench_driver_tick": 0.010,
        "no_host_span": 0.005, "bench_submit": 0.005})
    got = {name: _read(name, r) for name in READERS}
    assert got == pytest.approx({
        "idle_deadline_share.steady": 100 * 10 / 120,
        "idle_launch_host_share.steady": 100 * 25 / 120,
        "idle_unattributed_share.steady": 100 * 5 / 120})


def test_the_split_and_the_other_labels_sum_to_the_idle_share():
    r = trace.reduce_events(_synthetic())
    gaps = dict(r["idle_gaps"])
    rest = sum(v for n, v in gaps.items()
               if n == "wlsh_wait_idle" or n.startswith("bench_"))
    total = sum(_read(name, r) for name in READERS) + 100 * rest / 0.120
    assert total == pytest.approx(100 * r["idle_share"])


@pytest.mark.parametrize("name", READERS)
def test_a_label_without_idle_time_reads_zero(name):
    ev = _synthetic()
    ev["host"] = [h for h in ev["host"]
                  if h[0] in ("bench_trace_window", "bench_driver_tick")]
    ev["host"].append(["bench_submit", 0, 120_000_000])
    r = trace.reduce_events(ev)
    assert {n for n, _ in r["idle_gaps"]} == {"bench_submit",
                                               "bench_driver_tick"}
    assert _read(name, r) == 0.0


@pytest.mark.parametrize("name", READERS)
def test_a_run_without_a_trace_reads_none(name):
    assert _read(name, None) is None


def _unlabelled_inside(events: dict) -> float:
    """Idle seconds with no host span open, away from the window's edges.

    A span or device op that straddles the profiler's start or stop is
    not recorded, so only the first and last gaps may lack a label.
    """
    (w0, w1), = [(s, s + d) for n, s, d in events["host"]
                 if n == trace.WINDOW_SPAN]
    spans = [(s, s + d) for n, s, d in events["host"]
             if n != trace.WINDOW_SPAN]
    (ops,) = events["devices"].values()
    busy = trace._union([(max(s, w0), min(s + d, w1)) for _, s, d in ops
                         if min(s + d, w1) > max(s, w0)])
    edges = [t for iv in busy for t in iv]
    gaps = zip(edges[1::2], edges[2::2])  # between the first and last op
    return sum(b - a for a, b in gaps
               if not any(s <= (a + b) / 2 < e for s, e in spans)) / 1e9


def test_recorded_chip_trace_with_the_spans_reduces_to_its_known_answers():
    path = ROOT / "bench" / "testdata" / "v5e_trace_events_spans.json.gz"
    with gzip.open(path, "rt") as fh:
        rec = json.load(fh)
    r = trace.reduce_events(rec["events"])
    want = rec["reduced"]
    assert r["n_devices"] == 1
    for key in ("window_s", "busy_s", "idle_share"):
        assert r[key] == pytest.approx(want[key], rel=1e-12)
    assert dict(r["idle_gaps"]) == pytest.approx(dict(want["idle_gaps"]))
    # the program's spans label the idle time, within the ten labels kept
    labels = {n for n, _ in r["idle_gaps"]}
    assert {"wlsh_wait_idle", "wlsh_wait_deadline", "wlsh_readback"} <= labels
    assert len(labels) <= 10
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    got = {name: _read(name, r) for name in READERS}
    assert got == pytest.approx(rec["metrics"], rel=1e-9)
    # what no span covers lies at the window's edges; inside it, only
    # sub-microsecond gaps between the driver loop's spans
    assert dict(r["idle_gaps"])["no_host_span"] > 0.01
    assert _unlabelled_inside(rec["events"]) < 1e-5
