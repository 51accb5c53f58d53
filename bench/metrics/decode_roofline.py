"""Share of the roofline reached by the decode program (`ModelServer`'s
jitted `_decode_rows`, which serves prompt replay and decode alike), over
the calls in the traced window: the least time the chip could take for
the work the calls need (`model.decode_call_cost`: weights once, the live
rows' earlier K/V entries once, their new entries and logits written),
the larger of bytes over peak bandwidth and FLOPs over peak FLOP/s, over
the device time of those programs.  Decode is bandwidth-bound, so the
bytes set the least time."""

from benchkit import trace as tr
from benchkit.model import decode_call_cost


def read(run):
    if run.trace is None or not run.peaks:
        return None
    lo, hi = run.trace_host
    calls = [c for c in run.calls if c.t0 >= lo and c.t1 <= hi]
    secs, n = tr.program_time(run.trace, "_decode_rows")
    if not calls or not n:
        return None
    least = 0.0
    for c in calls:
        flops, nbytes = decode_call_cost(run.spec, c.contexts)
        least += max(nbytes / run.peaks["hbm_bytes_s"],
                     flops / run.peaks["bf16_flops"])
    # the host spans and the device programs are matched by count
    return least / secs * 100.0 * (n / len(calls))
