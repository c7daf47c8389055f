"""95th percentile of the frontend's queue wait, ms (program spans).

Span stage ``launch`` minus stage ``submit`` for every request submitted
in the window: batching delay plus waiting behind other launches.
"""

from bench.harness import percentile


def read(run):
    if not run.spans:
        return None
    return percentile([1e3 * (s["launch"] - s["submit"])
                       for s in run.spans], 95)
