"""Milliseconds per prompt token in `ModelServer.prefill_request` (the
prompt replay), from the harness's spans over the window."""


def read(run):
    secs, _, toks = run.span_sum("prefill")
    return secs / toks * 1e3 if toks else None
