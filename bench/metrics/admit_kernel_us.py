"""Device microseconds per engine step of the fused admission round
(`kernels.qos_admission.qos_round_fused`), from the traced window."""

from benchkit import trace as tr


def read(run):
    if run.trace is None:
        return None
    secs, n = tr.program_time(run.trace, "qos_round_fused")
    steps = sum(1 for name, _, _ in run.trace.host if name == "bench.step")
    return secs / steps * 1e6 if n and steps else None
