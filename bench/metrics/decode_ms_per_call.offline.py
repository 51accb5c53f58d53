"""Milliseconds per decode call of `ModelServer.step_fn` (the call, the
logits' copy to the host and their gathering), from the harness's spans
over the window."""


def read(run):
    secs, n, _ = run.span_sum("decode")
    return secs / n * 1e3 if n else None
