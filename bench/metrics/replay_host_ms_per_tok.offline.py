"""Host milliseconds per replayed prompt token in `ModelServer.
prefill_request`'s ``serve.replay`` phase (the per-token loop: inputs,
their copies to the device, the dispatch), from the program's phase
records in the traced window."""

from benchkit import phases


def read(run):
    recs = phases.records(run)
    if recs is None:
        return None
    secs, _, toks = phases.total(recs, "serve.replay")
    return secs / toks * 1e3 if toks else None
