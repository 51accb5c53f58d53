"""Milliseconds per decode call in `ModelServer.step_fn`'s ``serve.fetch``
phase (the logits' copy to the host and the gather of each row's), from
the program's phase records in the traced window."""

from benchkit import phases


def read(run):
    recs = phases.records(run)
    if recs is None:
        return None
    secs, calls, _ = phases.total(recs, "serve.fetch", least_n=1)
    return secs / calls * 1e3 if calls else None
