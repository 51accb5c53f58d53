"""Blocking device→host readbacks per engine step on the served host
path (the program's ``d2h`` count), from its phase records in the traced
window."""

from benchkit import phases


def read(run):
    return phases.per_step(run, "d2h", seconds=False)
