"""From the profiler's trace of a run to device busy time, per-program
device time, idle gaps and what the host was doing in them.

`load` reads the ``.xplane.pb`` that `jax.profiler` writes (with JAX's own
`ProfileData`) into a `DeviceTrace`: the device's op and program events,
and the harness's spans (``bench.*`` trace annotations) on the same
clock.  Everything after that is plain arithmetic on lists, checked on a
small recorded trace in `bench/tests`.
"""

from __future__ import annotations

import bisect
import itertools
import json
from dataclasses import dataclass, field

NS = 1e-9
PREFIX = "bench."  # harness spans in the trace
DISPATCH = "PjitFunction("  # the host's dispatch of a jitted call


@dataclass
class DeviceTrace:
    lo: int  # traced window on the trace clock (ns)
    hi: int
    ops: list = field(default_factory=list)  # (name, start, dur) device ops
    programs: list = field(default_factory=list)  # (name, start, dur)
    host: list = field(default_factory=list)  # (name, start, dur) harness
    devices: int = 1
    offset: int = 0  # ns added to device times to put them on the host's

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * NS

    def to_json(self) -> str:
        return json.dumps(self.__dict__)

    @classmethod
    def from_json(cls, text: str) -> "DeviceTrace":
        d = json.loads(text)
        return cls(**{k: ([tuple(e) for e in v] if isinstance(v, list) else v)
                      for k, v in d.items()})


def load(path: str, program: str = "_decode_rows") -> DeviceTrace:
    """Read one host's trace.  Device planes are ``/device:TPU:<n>``; on
    each, the ``XLA Ops`` line holds ops and ``XLA Modules`` the programs
    (whole jitted calls).  The window is the ``bench.window`` span.

    The device's times are put on the host's clock by `clock_offset`,
    from the runs of ``program`` and the harness spans that await them,
    so that device events and harness spans can be compared."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, programs, host = [], [], []
    devices = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices += 1
            for line in plane.lines:
                sink = {"XLA Ops": ops, "XLA Modules": programs}.get(line.name)
                if sink is None:
                    continue
                sink.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events
                            if ev.name.startswith((PREFIX, DISPATCH)))
    win = [h for h in host if h[0] == PREFIX + "window"]
    if not win or not devices:
        raise ValueError(f"{path}: no traced window or no TPU device plane")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    sent = sorted(s for n, s, _ in host if n == f"{DISPATCH}{program})")
    ran = sorted((s, s + d) for n, s, d in programs if f"jit_{program}(" in n)
    spans = [(s, s + d) for n, s, d in host
             if n in (PREFIX + "prefill", PREFIX + "decode")]
    off = clock_offset(sent, ran, spans)
    shift = lambda evs: [(n, s + off, d) for n, s, d in evs]  # noqa: E731
    return DeviceTrace(lo=lo, hi=hi, ops=shift(ops), programs=shift(programs),
                       host=[h for h in host if h[0].startswith(PREFIX)
                             and h[0] != PREFIX + "window"],
                       devices=devices, offset=off)


def clock_offset(sent: list[int], ran: list[tuple[int, int]],
                 spans: list[tuple[int, int]], slack: int = 200) -> int:
    """The offset that puts device times on the host's clock.  Every run
    of the program lies inside the harness span that issued and awaited
    it, so the offset is the one that puts most ``ran`` (start, end)
    inside ``spans``.  Candidates come from pairing the k-th dispatch
    with the k-th run, the lists slid against each other by up to
    ``slack`` calls (the host and the device tracers start and stop
    apart); ties go to the smaller offset.  0 when nothing pairs."""
    spans = sorted(spans)
    starts = [a for a, _ in spans]
    ends = [b for _, b in spans]
    reach = list(itertools.accumulate(ends, max))

    def inside(o):
        n = 0
        for a, b in ran:
            i = bisect.bisect_right(starts, a + o) - 1
            n += i >= 0 and reach[i] >= b + o
        return n

    cands = set()
    for j in range(-slack, slack + 1):
        d = sorted(sent[i + j] - a for i, (a, _) in enumerate(ran)
                   if 0 <= i + j < len(sent))
        if len(d) >= 3:
            cands.add(d[len(d) // 2])
    if not cands:
        return 0
    return int(max(sorted(cands, key=abs), key=inside))


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def busy_intervals(t: DeviceTrace) -> list[tuple[int, int]]:
    """Union of the device op intervals inside the window, merged."""
    iv = sorted((a, b) for _, a, b in _clip(t.ops, t.lo, t.hi))
    out: list[list[int]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(t: DeviceTrace) -> float:
    """Seconds in which some op ran, averaged over the traced devices."""
    return sum(b - a for a, b in busy_intervals(t)) * NS / max(t.devices, 1)


def program_time(t: DeviceTrace, needle: str) -> tuple[float, int]:
    """(device seconds, count) of the programs whose name contains
    ``needle``, inside the window."""
    sel = [(a, b) for n, a, b in _clip(t.programs, t.lo, t.hi) if needle in n]
    return sum(b - a for a, b in sel) * NS, len(sel)


def idle_gaps(t: DeviceTrace) -> list[tuple[int, int]]:
    busy = busy_intervals(t)
    gaps, cur = [], t.lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t.hi > cur:
        gaps.append((cur, t.hi))
    return gaps


def host_activity(t: DeviceTrace, a: int, b: int) -> dict[str, float]:
    """Seconds of [a, b) under each innermost harness span; the rest is
    the harness's own loop (``generator``)."""
    spans = sorted(((s, s + d, n[len(PREFIX):]) for n, s, d in t.host
                    if s < b and s + d > a), key=lambda x: (x[0], -x[1]))
    cuts = sorted({a, b} | {max(a, min(b, x)) for s, e, _ in spans
                            for x in (s, e)})
    out: dict[str, float] = {}
    for lo, hi in zip(cuts, cuts[1:]):
        mid = (lo + hi) / 2
        inner = [(e - s, n) for s, e, n in spans if s <= mid < e]
        name = min(inner)[1] if inner else "generator"
        out[name] = out.get(name, 0.0) + (hi - lo) * NS
    return out


def idle_by_activity(t: DeviceTrace) -> list[list]:
    """Idle device seconds by what the host was doing, longest first."""
    tot: dict[str, float] = {}
    for a, b in idle_gaps(t):
        for name, s in host_activity(t, a, b).items():
            tot[name] = tot.get(name, 0.0) + s
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])]


def op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` → ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def program_name(name: str) -> str:
    """``jit__decode_rows(1234)`` → ``jit__decode_rows``."""
    return name.split("(", 1)[0]


def self_times(events) -> list[tuple[str, int, int, int]]:
    """(name, start, end, self ns) of nested events on one line: an op
    that contains others (a loop around its body) keeps only the time no
    op inside it covers."""
    evs = sorted(((n, s, s + d) for n, s, d in events),
                 key=lambda e: (e[1], -e[2]))
    out = [[n, a, b, b - a] for n, a, b in evs]
    stack: list[int] = []
    for i, (_, a, b, _) in enumerate(out):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack:  # inside the op on top of the stack, for the overlap
            out[stack[-1]][3] -= min(b, out[stack[-1]][2]) - a
        stack.append(i)
    return [tuple(e) for e in out]


def top_ops(t: DeviceTrace, k: int = 10) -> list[list]:
    """The ``k`` device ops with the most self time in the window, each
    named ``<program>/<op>`` by the program it ran in."""
    progs = sorted((s, s + d, program_name(n)) for n, s, d in t.programs)
    starts = [p[0] for p in progs]
    clipped = [(n, a, b - a) for n, a, b in _clip(t.ops, t.lo, t.hi)]
    tot: dict[str, float] = {}
    for n, a, _, own in self_times(clipped):
        i = bisect.bisect_right(starts, a) - 1
        prog = progs[i][2] if i >= 0 and a < progs[i][1] else "?"
        key = f"{prog}/{op_name(n)}"
        tot[key] = tot.get(key, 0.0) + own * NS
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]
