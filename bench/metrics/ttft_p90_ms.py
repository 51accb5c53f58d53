"""90th percentile of time to first token over every request due in the
window, from its due time to the step after which the harness saw its
first token; one still waiting at the close counts at its age then."""

from benchkit.record import percentile, ttft_samples


def read(run):
    v = percentile(ttft_samples(run), 90)
    return None if v is None else v * 1e3
