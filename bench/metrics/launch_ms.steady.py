"""Mean compiled-step launch, ms: ``Profiler`` dispatch time per launch.

Wall time from the call of the compiled step to the host readback of its
outputs, summed over the window's launches and divided by their count.
"""


def read(run):
    d = run.dispatch
    if not d or not d["launches"]:
        return None
    return 1e3 * d["seconds"] / d["launches"]
