"""90th percentile, over the requests that produced at least two tokens
in the window, of each one's mean gap between output tokens."""

from benchkit.record import percentile, tpot_samples


def read(run):
    v = percentile(tpot_samples(run), 90)
    return None if v is None else v * 1e3
