"""Median latency of every request due in the window, ms (host clock).

From each request's due time to the resolution of its future; an
unanswered request counts as infinitely late.
"""

from bench.harness import percentile


def read(run):
    return percentile(run.window.latency_ms, 50)
