"""Host milliseconds per engine step in the scheduler's
``sched.telemetry`` phase (the round's telemetry sample, with its three
QoS readbacks, and its recording), from the program's phase records in
the traced window."""

from benchkit import phases


def read(run):
    v = phases.per_step(run, "sched.telemetry")
    return None if v is None else v * 1e3
