"""What a run records, as the metric readers see it.

Times are host `time.perf_counter` seconds.  Spans are the harness's own,
around the calls it makes into the program:

    step     `ContinuousBatchingEngine.step`
    prefill  the engine's ``prefill_fn`` (`ModelServer.prefill_request`),
             ``n`` = prompt tokens replayed
    decode   the engine's ``step_fn`` when it runs a decode call,
             ``n`` = rows decoded
    sample   the engine's ``sample_fn`` (argmax on the host)

Every decode call (``kind`` "decode", one per decode span) is kept with
the positions of the rows that write a live entry, and so is every prompt
token (``kind`` "prefill", one record per token, at its position), so that
the operations and bytes they need can be counted (the family's
``call_cost``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional


@dataclass
class Span:
    name: str
    t0: float
    t1: float
    n: int = 0


@dataclass
class Call:
    t0: float
    t1: float
    contexts: list[int]
    kind: str  # "decode" | "prefill"


@dataclass
class ReqRecord:
    rid: int
    tenant: str
    prompt_len: int
    max_new: int
    due: Optional[float] = None  # open loop: when it was due to be sent
    submit: float = 0.0
    first: Optional[float] = None  # first token seen in the window
    last: Optional[float] = None  # last token seen in the window
    n_window: int = 0  # tokens seen in the window
    seen: int = 0  # tokens seen so far


@dataclass
class Run:
    cell: str
    config: dict
    spec: object  # the family's spec of the configuration
    peaks: dict
    t_open: float
    t_close: float
    setup_s: float
    requests: list[ReqRecord]
    spans: list[Span]
    calls: list[Call]
    steps: int  # engine steps in the window
    out_tokens: int  # tokens produced in the window
    trace: object = None  # trace.DeviceTrace of the traced run
    trace_host: tuple = (0.0, 0.0)  # the traced window on the host clock
    family: object = None  # the configuration's family module (`spec.Bench`)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def span_sum(self, name: str) -> tuple[float, int, int]:
        """(seconds, spans, sum of n) of the spans named ``name``."""
        sel = [s for s in self.spans if s.name == name]
        return (sum(s.t1 - s.t0 for s in sel), len(sel),
                sum(s.n for s in sel))


def percentile(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) by linear interpolation between
    closest ranks, as numpy's default; None for no values."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def ttft_samples(run: Run) -> list[float]:
    """Seconds from due to first token of every request due in the window;
    one still waiting at the close counts at its age then."""
    out = []
    for r in run.requests:
        if r.due is None or not (run.t_open <= r.due < run.t_close):
            continue
        out.append((r.first if r.first is not None else run.t_close) - r.due)
    return out


def tpot_samples(run: Run) -> list[float]:
    """Mean gap between output tokens of every request that produced at
    least two tokens in the window."""
    return [(r.last - r.first) / (r.n_window - 1)
            for r in run.requests if r.n_window >= 2]
