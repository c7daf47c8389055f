"""95th percentile of how late the load generator called submit, ms.

A generator held back (by a full batch launching inside ``submit``, or
by the driver's lock) shows here and not as a fast server.
"""

from bench.harness import percentile


def read(run):
    return percentile(run.window.gen_late_ms, 95)
