"""device_idle_share (%, device): 1 - the busy union of device operations
over the traced window, the mean over the chips used.  Moves itl_p95_s."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
