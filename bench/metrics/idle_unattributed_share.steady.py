"""Device idle time that no host span covers, % of the traced window.

Idle gaps with no ``bench_*`` or ``wlsh_*`` span open at their midpoint:
what the program's spans do not account for (the driver loop between
its spans, threads the program does not annotate).
"""


def read(run):
    if run.trace is None:
        return None
    idle_s = sum(s for label, s in run.trace["idle_gaps"]
                 if label == "no_host_span")
    return 100.0 * idle_s / run.trace["window_s"]
