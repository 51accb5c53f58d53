"""Share of the roofline reached by the decode program (`ModelServer`'s
jitted `_decode_rows`) over the decode calls in the traced window: the
least time the chip could take for the work those calls need (the
family's ``call_cost``: for a dense decoder, weights once, the live rows'
earlier K/V entries once, their new entries and logits written), the
larger of bytes over peak bandwidth and FLOPs over peak FLOP/s, over the
device time of those programs.  Prompt tokens run in other programs and
are left out.  Decode is bandwidth-bound, so the bytes set the least
time."""

from benchkit import trace as tr


def read(run):
    if run.trace is None or not run.peaks:
        return None
    lo, hi = run.trace_host
    calls = [c for c in run.calls
             if c.kind == "decode" and c.t0 >= lo and c.t1 <= hi]
    secs, n = tr.program_time(run.trace, "_decode_rows")
    if not calls or not n:
        return None
    least = 0.0
    for c in calls:
        flops, nbytes = run.family.call_cost(run.spec, c)
        least += max(nbytes / run.peaks["hbm_bytes_s"],
                     flops / run.peaks["bf16_flops"])
    # the host's decode calls and the device's programs are matched by count
    return least / secs * 100.0 * (n / len(calls))
