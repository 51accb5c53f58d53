"""Device milliseconds per prompt-chunk program (`ModelServer`'s jitted
`_prefill_chunk`: one row's 256-token chunk through every layer, its
Mamba-2 state in and out) in the traced window.  None unless the
program's own ``prefill_chunk`` count in the window equals the number of
those programs there (a chunk at the window's edge, or none in it)."""

from benchkit import phases
from benchkit import trace as tr


def read(run):
    recs = phases.records(run)
    if recs is None:
        return None
    secs, n = tr.program_time(run.trace, "_prefill_chunk")
    counted = phases.total(recs, "prefill_chunk")[2]
    return secs / n * 1e3 if n and n == counted else None
