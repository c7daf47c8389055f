"""Set-up seconds: process start to the window's opening (host clock).

Covers JAX's start, the corpus, the host planner, the device states, the
compiled steps (or their load from the cache) and one launch per group.
"""


def read(run):
    return run.setup_s
