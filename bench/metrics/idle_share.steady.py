"""Device idle share of the traced part of the window, % (device trace).

1 - (union of device op intervals) / (traced window).
"""


def read(run):
    if run.trace is None or not run.trace["n_devices"]:
        return None
    return 100.0 * run.trace["idle_share"]
