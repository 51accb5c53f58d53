"""Share of the traced window in which no op ran on the device."""

from benchkit import trace as tr


def read(run):
    if run.trace is None:
        return None
    return (1.0 - tr.busy_s(run.trace) / run.trace.window_s) * 100.0
