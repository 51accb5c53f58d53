"""Host milliseconds per engine step in the scheduler's ``sched.admit``
phase (the admission round over the backlog, with its readbacks, and the
order of the admitted), from the program's phase records in the traced
window."""

from benchkit import phases


def read(run):
    v = phases.per_step(run, "sched.admit")
    return None if v is None else v * 1e3
