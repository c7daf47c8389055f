"""Device idle time while a request waits for its batching deadline, %.

Idle gaps of the traced window whose innermost host span is the driver's
``wlsh_wait_deadline`` (asleep with a request pending, until its
deadline or the next submit), over the traced window.  A launch that
starts from idle pays this delay; a shorter deadline or a launch on the
first request would move it.
"""


def read(run):
    if run.trace is None:
        return None
    idle_s = sum(s for label, s in run.trace["idle_gaps"]
                 if label == "wlsh_wait_deadline")
    return 100.0 * idle_s / run.trace["window_s"]
