"""The program's own phase records in a traced run.

While the profiler runs, the engine and `ModelServer` time their phases
(``sched.*``, ``serve.*`` spans) and count each blocking device→host
readback (``d2h``), on `time.perf_counter_ns`, the harness's clock
(`repro.obs.trace.span`, ``count``, ``phase_records``).  A record is
``(name, t0_ns, t1_ns, parent, n)``; a count is one of zero length with
the amount in ``n``.  The readers take the records inside the traced
window, ``run.trace_host``.

Device times are put on the same clock by the ``bench.window`` anchor:
the trace's window start ``run.trace.lo`` is the harness's
``run.trace_host[0]``, read just after the annotation opened.

Where the run was not traced, or the program keeps no phase records (a
program older than them), `records` is None and so is every metric that
reads it.
"""

from __future__ import annotations

import bisect
import itertools
import math
import statistics

from . import trace as tr

NS = 1e-9


def records(run):
    """The program's records wholly inside the traced window, or None."""
    if run.trace is None:
        return None
    try:
        from repro.obs.trace import phase_records
    except ImportError:
        return None
    lo, hi = run.trace_host
    return phase_records(math.floor(lo * 1e9), math.ceil(hi * 1e9)) or None


def total(recs, name: str, least_n: int = 0) -> tuple[float, int, int]:
    """(seconds, records, sum of n) of the records named ``name`` whose
    ``n`` is at least ``least_n``."""
    sel = [r for r in recs if r[0] == name and r[4] >= least_n]
    return (sum(r[2] - r[1] for r in sel) * NS, len(sel),
            sum(r[4] for r in sel))


def per_step(run, name: str, seconds: bool = True):
    """Seconds (or, ``seconds`` False, the summed ``n`` of a count) of
    ``name`` per engine step (``sched.step`` span) in the window."""
    recs = records(run)
    if recs is None:
        return None
    steps = total(recs, "sched.step")[1]
    secs, _, n = total(recs, name)
    return (secs if seconds else n) / steps if steps else None


def leaves(recs) -> list[tuple[int, int, str]]:
    """(start, end, name) of the leaf spans: spans no other span names as
    its parent.  Counts (zero length) are not spans."""
    parents = {r[3] for r in recs if r[2] > r[1]}
    return sorted((a, b, n) for n, a, b, _, _ in recs
                  if b > a and n not in parents)


def _anchor(run) -> int:
    """ns to add to a trace time to put it on `time.perf_counter_ns`."""
    return round(run.trace_host[0] * 1e9) - run.trace.lo


def idle_by_leaf(run):
    """Idle device seconds in the traced window by the leaf span open on
    the host at the time; under ``None`` the idle time no leaf span
    covers.  None where there are no records."""
    recs = records(run)
    if recs is None:
        return None
    lv = leaves(recs)
    starts = [a for a, _, _ in lv]
    reach = list(itertools.accumulate((b for _, b, _ in lv), max))
    off = _anchor(run)
    out: dict = {None: 0.0}
    for a, b in tr.idle_gaps(run.trace):
        a, b = a + off, b + off
        cover = []
        i = bisect.bisect_left(starts, b) - 1
        while i >= 0 and reach[i] > a:
            s, e, name = lv[i]
            s, e = max(s, a), min(e, b)
            if e > s:
                out[name] = out.get(name, 0.0) + (e - s) * NS
                cover.append((s, e))
            i -= 1
        named, cur = 0, a
        for s, e in sorted(cover):  # union: leaves of other threads overlap
            s = max(s, cur)
            if e > s:
                named += e - s
                cur = e
        out[None] += (b - a - named) * NS
    return out


def anchor_error_s(run):
    """How far the ``bench.window`` anchor puts the trace's ``bench.step``
    spans from the harness's own ``step`` records: the median, over the
    steps of the window, of the larger of the start and end differences,
    in seconds.  None where the run was not traced or nothing pairs."""
    if run.trace is None:
        return None
    off = _anchor(run)
    traced = sorted((s + off, s + d + off) for n, s, d in run.trace.host
                    if n == tr.PREFIX + "step")
    lo, hi = run.trace_host
    mine = [(s.t0 * 1e9, s.t1 * 1e9) for s in run.spans
            if s.name == "step" and s.t0 >= lo and s.t1 <= hi]
    if not traced or len(traced) != len(mine):
        return None
    return statistics.median(max(abs(a - c), abs(b - d)) for (a, b), (c, d)
                             in zip(traced, mine)) * NS
