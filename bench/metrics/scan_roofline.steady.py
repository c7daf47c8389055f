"""Scan kernels' share of the HBM roofline, % (device trace).

The least time the traced launches need, one read of each launched
group's live codes (for the widest member of the batch) and vectors at
the published HBM bandwidth (``bench/work.py``), over the device busy
time of the traced part of the window.  Bytes bound it: the kernels'
int32/VPU work has no published peak.
"""


def read(run):
    if (run.trace is None or not run.trace["busy_s"]
            or not run.scan_bytes_traced):
        return None
    least_s = run.scan_bytes_traced / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace["busy_s"]
