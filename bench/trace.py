"""Reduction of a profiler trace to device busy time, idle gaps and ops.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain event lists; ``reduce_events`` works on those lists alone, so the
test suite checks it on a trace recorded on the chip and kept under
``bench/testdata``.

- device events: the operations on the ``XLA Ops`` line of each device
  plane (planes without one, such as an empty ``Megascale Trace`` plane,
  are no chips), named by their HLO result (``%fusion.3``);
- host events: the spans of the benchmark (``bench_*``) and of the
  program (``wlsh_*``) on the host planes;
- the window: the host span ``bench_trace_window``.

Busy time is the union of the device events' intervals inside the
window, averaged over devices; each idle gap is labelled by the innermost
host span open at its midpoint.
"""

from __future__ import annotations

import collections
import glob
import os
import re

__all__ = ["WINDOW_SPAN", "load_xplane", "reduce_events"]

WINDOW_SPAN = "bench_trace_window"
_HOST_PREFIXES = ("bench_", "wlsh_")


def load_xplane(trace_dir: str) -> dict:
    """Device and host events of the one profile under ``trace_dir``.

    Returns ``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``.
    """
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    prof = ProfileData.from_file(paths[0])
    devices: dict[str, list] = {}
    host: list = []
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            if ops:
                devices[plane.name] = [
                    [ev.name.split(" = ")[0], int(ev.start_ns),
                     int(ev.duration_ns)] for ev in ops[0].events]
        elif plane.name.startswith("/host:"):
            host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ln in plane.lines for ev in ln.events
                        if ev.name.startswith(_HOST_PREFIXES))
    return {"devices": devices, "host": host}


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label(name: str) -> str:
    """A host span's name without its argument (``f[sig]`` -> ``f``)."""
    return re.sub(r"\[.*$", "", name)


def reduce_events(events: dict, top: int = 10) -> dict:
    """Busy/idle accounting of a traced window (see the module docstring).

    Returns ``n_devices``, ``window_s``, ``busy_s`` (mean over devices),
    ``idle_share``, ``device_ops`` (the ``top`` ops by device seconds,
    summed over devices) and ``idle_gaps`` (idle seconds by host span,
    largest first, ``top`` at most); times in seconds.
    """
    wins = [(s, s + d) for n, s, d in events["host"] if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} host span, "
                         f"found {len(wins)}")
    w0, w1 = wins[0]
    spans = [(s, s + d, _label(n)) for n, s, d in events["host"]
             if n != WINDOW_SPAN]
    busy_ns = []
    op_ns: collections.Counter = collections.Counter()
    gap_ns: collections.Counter = collections.Counter()
    for evs in events["devices"].values():
        clipped = []
        for name, s, d in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                op_ns[name] += b - a
        busy = _union(clipped)
        busy_ns.append(sum(b - a for a, b in busy))
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            open_ = [(e - s, lab) for s, e, lab in spans if s <= mid < e]
            gap_ns[min(open_)[1] if open_ else "no_host_span"] += b - a
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9 if busy_ns else 0.0
    return {
        "n_devices": len(busy_ns),
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "device_ops": [[n, v / 1e9] for n, v in op_ns.most_common(top)],
        "idle_gaps": [[n, v / 1e9 / max(len(busy_ns), 1)]
                      for n, v in gap_ns.most_common(top)],
    }
