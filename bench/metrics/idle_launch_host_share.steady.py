"""Device idle time under a launch's host work, % of the traced window.

Idle gaps whose innermost host span is one of a launch's stages: the
state lease, the host query encode, the compiled-step dispatch (input
copies), the readback and the resolve (merge, counters, futures).  The
chip waits on the host here while a request is being served.
"""

_LAUNCH_SPANS = ("wlsh_lease", "wlsh_encode", "wlsh_query_step",
                 "wlsh_readback", "wlsh_resolve")


def read(run):
    if run.trace is None:
        return None
    idle_s = sum(s for label, s in run.trace["idle_gaps"]
                 if label in _LAUNCH_SPANS)
    return 100.0 * idle_s / run.trace["window_s"]
