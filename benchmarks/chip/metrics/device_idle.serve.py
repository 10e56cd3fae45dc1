"""Share of the traced window in which no operation ran on the device."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0 or not run.trace.busy_s:
        return None
    return 100.0 * (1.0 - run.trace.mean_busy_s / run.trace.window_s)
