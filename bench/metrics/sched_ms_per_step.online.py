"""Host self time of `ContinuousBatchingEngine.step` per step: its wall
time less the time in prefill, decode and sampling, over the window."""


def read(run):
    step, n, _ = run.span_sum("step")
    inner = sum(run.span_sum(k)[0] for k in ("prefill", "decode", "sample"))
    return (step - inner) / n * 1e3 if n else None
