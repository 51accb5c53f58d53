"""Seconds from process start to the window's start: imports, weights,
compilation or compile-cache loads, warm-up and the first wave."""


def read(run):
    return run.setup_s
