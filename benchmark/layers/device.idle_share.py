"""device.idle_share: percent of the traced window in which no operation
ran on the device (1 - busy / window, from the profiler trace)."""


def read(run):
    if run.summary is None:
        return None
    return 100.0 * (1.0 - run.summary.busy_s / run.summary.window_s)
