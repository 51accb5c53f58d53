"""Output tokens produced in the window over the window's length."""


def read(run):
    return run.out_tokens / run.window_s
