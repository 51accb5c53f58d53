"""Share of the traced window in which the device is idle and no leaf
phase span of the program (`repro.obs.trace.span`) is open on the host:
idle time that nothing names.  Device times are put on the host's clock
by the ``bench.window`` anchor (`benchkit.phases`)."""

from benchkit import phases


def read(run):
    idle = phases.idle_by_leaf(run)
    if idle is None:
        return None
    return idle[None] / run.trace.window_s * 100.0
