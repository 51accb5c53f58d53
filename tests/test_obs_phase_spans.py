"""Phase spans and counts (`repro.obs.trace.span` / ``count``) inside the
engine's host ``step()`` and `launch.serve.ModelServer`, and the
benchmark's readers of them (`bench/benchkit/phases.py`,
``bench/metrics/*``).

Spans record only while a JAX profiler session runs; each lands in the
profiler's trace as a ``repro.<name>`` annotation and in the bounded
`PHASES` log on `time.perf_counter_ns`.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import pytest

from repro.configs.registry import get_smoke_config
from repro.launch.serve import ModelServer
from repro.obs import trace as ot
from repro.serving.scheduler import ContinuousBatchingEngine, Request

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from benchkit import phases  # noqa: E402
from benchkit.record import Run, Span  # noqa: E402
from benchkit.spec import Bench  # noqa: E402
from benchkit.trace import DeviceTrace  # noqa: E402

_ARGMAX = lambda lg: lg.argmax(-1)  # noqa: E731
SLOTS = 4


@pytest.fixture
def log(monkeypatch):
    """A fresh phase log in `PHASES`' place."""
    fresh = ot.PhaseLog()
    monkeypatch.setattr(ot, "PHASES", fresh)
    return fresh


@pytest.fixture(scope="module")
def server():
    return ModelServer(get_smoke_config("qwen2-0.5b"), SLOTS, max_len=64)


def _engine(server):
    return ContinuousBatchingEngine(
        server.step_fn, server.prefill_request, SLOTS,
        tenants={"a": 2.0, "b": 1.0}, use_kernel=True)


def _reqs(rid0, prompts, new=3):
    return [Request(rid=rid0 + i, prompt=p, max_new_tokens=new,
                    tenant_id="ab"[i % 2]) for i, p in enumerate(prompts)]


# ------------------------------------------------------------ the program ----


def test_no_profiler_session_records_nothing(server, log):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    eng = _engine(server)
    eng.submit_batch(_reqs(0, [[1, 2, 3], [4, 5]]))
    for _ in range(4):
        eng.step(_ARGMAX)
    assert len(log) == 0 and log.total == 0
    assert ot.span("sched.step") is ot.span("serve.decode")  # one no-op


def _under(recs, outer):
    """Records that lie inside the span record ``outer``."""
    return [r for r in recs if outer[1] <= r[1] and r[2] <= outer[2]
            and r is not outer]


def test_a_traced_step_records_the_span_tree(server, log, tmp_path):
    eng = _engine(server)
    eng.submit_batch(_reqs(0, [[1, 2, 3], [4, 5]]))
    eng.step(_ARGMAX)  # admits and prefills both: every program compiled
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step(_ARGMAX)  # decodes both; nothing left in the backlog
        eng.submit_batch(_reqs(2, [[7, 8, 9, 10]]))
        eng.step(_ARGMAX)  # admits and prefills one, decodes two
    finally:
        jax.profiler.stop_trace()
    recs = log.records(0, 2**63)
    steps = [r for r in recs if r[0] == "sched.step"]
    assert len(steps) == 2 and all(r[3] is None for r in steps)
    parent = {"sched.admit": "sched.step", "sched.nonfinite": "sched.step",
              "sched.sample": "sched.step", "sched.finish": "sched.step",
              "sched.telemetry": "sched.step", "serve.prefill": "sched.step",
              "serve.decode": "sched.step", "serve.reset": "serve.prefill",
              "serve.replay": "serve.prefill",
              "serve.prompt_logits": "serve.prefill",
              "serve.feed": "serve.decode", "serve.wait": "serve.decode",
              "serve.fetch": "serve.decode"}
    spans = [r for r in recs if r[0] not in ("d2h", "prefill_chunk")]
    assert {r[0] for r in spans} == set(parent) | {"sched.step"}
    for r in spans:
        assert r[2] >= r[1]
        if r[0] != "sched.step":
            assert r[3] == parent[r[0]], r
    first, second = steps
    names1 = [r[0] for r in _under(recs, first)
              if r[0] not in ("d2h", "prefill_chunk")]
    assert "serve.prefill" not in names1
    assert names1.count("serve.decode") == 1
    prefill = [r for r in _under(recs, second) if r[0] == "serve.prefill"]
    assert [r[4] for r in prefill] == [4]  # n: the prompt's tokens
    assert [r[4] for r in _under(recs, prefill[0])
            if r[0] == "serve.replay"] == [4]
    # one chunk call for the 4-token prompt, counted inside the replay
    assert [(r[3], r[4]) for r in recs if r[0] == "prefill_chunk"] == [
        ("serve.replay", 1)]
    for step in steps:
        assert [r[4] for r in _under(recs, step)
                if r[0] in ("serve.decode", "serve.fetch")] == [2, 2]
    # the blocking device→host readbacks of the kernel-QoS path
    d2h = [sum(r[4] for r in _under(recs, s) if r[0] == "d2h")
           for s in steps]
    # empty backlog: telemetry 3, decode logits 1
    # admission: kernel round 3 (leftover, admitted, expired), grant for
    # the FCFS sort 1, grant for the slot order 1, prompt logits 1, then
    # decode logits 1 and telemetry 3
    assert d2h == [3 + 1, 3 + 1 + 1 + 1 + 1 + 3]
    assert all(r[1] == r[2] for r in recs if r[0] == "d2h")
    # the same spans in the profiler's own trace
    from jax.profiler import ProfileData

    pb = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    names = {ev.name for plane in ProfileData.from_file(str(pb)).planes
             for line in plane.lines for ev in line.events}
    assert {"repro." + n for n in parent} | {"repro.sched.step"} <= names


@pytest.mark.parametrize("n", [1, 255, 256, 257, 515])
def test_prefill_counts_one_chunk_call_per_chunk(n, log, tmp_path):
    """``prefill_chunk`` counts ceil(n / chunk) calls for an n-token
    prompt, and the prompt's logits are still one readback."""
    srv = ModelServer(get_smoke_config("qwen2-0.5b"), 1, max_len=600)
    assert srv.chunk == 256
    req = Request(rid=0, prompt=[1 + i % 7 for i in range(n)],
                  max_new_tokens=1)
    req.slot = 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.prefill_request(req)
    finally:
        jax.profiler.stop_trace()
    recs = log.records(0, 2**63)
    assert sum(r[4] for r in recs if r[0] == "prefill_chunk") == -(-n // 256)
    assert [(r[3], r[4]) for r in recs if r[0] == "d2h"] == [
        ("serve.prompt_logits", 1)]


def test_a_traced_megastep_records_its_launch_and_drain(log, tmp_path):
    from repro.serving.engine_state import rid_token_fn

    eng = ContinuousBatchingEngine(lambda act: None, lambda r: None, 2,
                                   tenants={"a": 1.0})
    eng.submit_batch([Request(rid=i, prompt=[1], max_new_tokens=3,
                              tenant_id="a") for i in range(3)])
    eng.megastep(2, token_fn=rid_token_fn)  # compiled outside the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.megastep(2, token_fn=rid_token_fn)
    finally:
        jax.profiler.stop_trace()
    assert [(r[0], r[3], r[4]) for r in log.records(0, 2**63)] == [
        ("sched.megastep", None, 0)]


def test_the_log_drops_the_oldest_records_and_counts_them():
    log = ot.PhaseLog(capacity=3)
    for name in ("a", "b", "c"):
        with log.span(name, n=1):
            pass
    log.count("d2h", 2)
    with log.span("e"):
        log.count("d2h")
    assert len(log) == 3 and log.total == 6 and log.dropped == 3
    assert [r[0] for r in log.records(0, 2**63)] == ["d2h", "d2h", "e"]
    assert [r[3:] for r in log.records(0, 2**63)] == [
        (None, 2), ("e", 1), (None, 0)]


def test_records_are_read_by_window():
    log = ot.PhaseLog()
    for rec in [("a", 10, 20, None, 0), ("b", 15, 30, None, 0),
                ("c", 25, 25, None, 1)]:
        log.append(rec)
    assert [r[0] for r in log.records(10, 25)] == ["a", "c"]
    assert [r[0] for r in log.records(11, 40)] == ["b", "c"]


# ------------------------------------------------------------ the readers ----

LO = 7_000_000  # the traced window on the trace's clock (ns)
HOST0 = 1234.5  # the same instant on perf_counter (s)
B = round(HOST0 * 1e9)  # ... in ns
W = 1_000_000  # window length (ns)


def _recs():
    """Two engine steps of a synthetic window (perf_counter ns from B)."""
    out = []
    for base, admit in ((0, True), (500_000, False)):
        out.append(("sched.step", B + base, B + base + 400_000, None, 0))
        if admit:
            out += [("sched.admit", B + 10_000, B + 60_000, "sched.step", 0),
                    ("d2h", B + 20_000, B + 20_000, "sched.admit", 3),
                    ("d2h", B + 30_000, B + 30_000, "sched.admit", 1),
                    ("serve.prefill", B + 60_000, B + 100_000, "sched.step",
                     4),
                    ("serve.replay", B + 60_000, B + 90_000, "serve.prefill",
                     4)]
        b = B + base
        out += [("serve.decode", b + 100_000, b + 380_000, "sched.step", 2),
                ("serve.feed", b + 100_000, b + 120_000, "serve.decode", 0),
                ("serve.wait", b + 120_000, b + 300_000, "serve.decode", 0),
                ("serve.fetch", b + 300_000, b + 380_000, "serve.decode", 2),
                ("d2h", b + 310_000, b + 310_000, "serve.fetch", 1),
                ("sched.telemetry", b + 380_000, b + 390_000, "sched.step",
                 0),
                ("d2h", b + 385_000, b + 385_000, "sched.telemetry", 3)]
    return out


def _run(trace=True, host_steps=None):
    t = DeviceTrace(
        lo=LO, hi=LO + W,
        # busy: the admission kernel, and each step's decode call
        ops=[("k", LO + 15_000, 10_000), ("d", LO + 120_000, 180_000),
             ("d", LO + 620_000, 180_000)],
        programs=[],
        host=host_steps or [])
    spans = [Span("step", HOST0 + 0.0, HOST0 + 400e-6),
             Span("step", HOST0 + 500e-6, HOST0 + 900e-6)]
    return Run(cell="c", config={}, spec=None, peaks={}, t_open=HOST0 - 40,
               t_close=HOST0 + W * 1e-9, setup_s=1.0, requests=[],
               spans=spans, calls=[], steps=2, out_tokens=0,
               trace=t if trace else None,
               trace_host=(HOST0, HOST0 + W * 1e-9))


@pytest.fixture
def synthetic(log):
    for rec in _recs():
        log.append(rec)
    log.append(("sched.step", B - 10, B + 5, None, 0))  # outside the window
    return _run()


def _read(name, run):
    return Bench(ROOT).reader(name)(run)


def test_the_device_idle_split_by_leaf_span(synthetic):
    idle = {k: round(v * 1e9) for k, v in
            phases.idle_by_leaf(synthetic).items()}
    # idle gaps (from the window's start): 0-15k, 25k-120k, 300k-620k and
    # 800k-1000k ns; the leaves of each step as `_recs` lays them out
    assert idle == {"sched.admit": 5_000 + 35_000,
                    "serve.replay": 30_000,
                    "serve.feed": 20_000 + 20_000,
                    "serve.fetch": 80_000 + 80_000,
                    "sched.telemetry": 10_000 + 10_000,
                    # serve.wait: the device was busy throughout
                    # not under a leaf: the prefill's own 10k, the gaps
                    # between steps, and the window's head and tail
                    None: 630_000 - 290_000}


def test_the_split_follows_the_window_anchor(log):
    """Moved along the trace's clock, the device's busy time moves with
    the window: the idle split is read through ``bench.window``."""
    for rec in _recs():
        log.append(rec)
    run = _run()
    shifted = _run()
    k = 123_456_789
    t = shifted.trace
    shifted.trace = DeviceTrace(lo=t.lo + k, hi=t.hi + k,
                                ops=[(n, s + k, d) for n, s, d in t.ops])
    assert phases.idle_by_leaf(shifted) == phases.idle_by_leaf(run)


@pytest.mark.parametrize("kind", ["online", "offline"])
def test_the_metric_readers(synthetic, kind):
    # fetch: 80 µs in each of two decode calls
    assert _read(f"logits_fetch_ms_per_call.{kind}", synthetic) == \
        pytest.approx(0.080)
    idle = phases.idle_by_leaf(synthetic)[None]
    assert _read(f"device_idle_unnamed_pct.{kind}", synthetic) == \
        pytest.approx(idle / (W * 1e-9) * 100)
    if kind == "offline":
        assert _read("replay_host_ms_per_tok.offline", synthetic) == \
            pytest.approx(0.030 / 4)
        assert _read("admit_ms_per_step.offline", synthetic) == \
            pytest.approx(0.050 / 2)
        assert _read("telemetry_ms_per_step.offline", synthetic) == \
            pytest.approx(0.010)
        assert _read("d2h_per_step.offline", synthetic) == \
            pytest.approx((3 + 1 + 1 + 3 + 1 + 3) / 2)


NEW = ["logits_fetch_ms_per_call.online", "logits_fetch_ms_per_call.offline",
       "replay_host_ms_per_tok.offline", "admit_ms_per_step.offline",
       "telemetry_ms_per_step.offline", "d2h_per_step.offline",
       "device_idle_unnamed_pct.online", "device_idle_unnamed_pct.offline"]


@pytest.mark.parametrize("name", NEW)
def test_the_readers_read_nothing_without_records(name, log, monkeypatch):
    """Untraced, or a program that keeps no phase records: no number, no
    error."""
    assert _read(name, _run(trace=False)) is None
    assert _read(name, _run()) is None  # traced, nothing recorded
    for rec in _recs():
        log.append(rec)
    assert _read(name, _run()) is not None
    monkeypatch.delattr(ot, "phase_records")
    assert _read(name, _run()) is None


def test_the_new_metrics_are_listed_where_they_read():
    b = Bench(ROOT)
    listed = {m["name"]: m for m in b.doc["per_layer"]}
    for name in NEW:
        m = listed[name]
        e2e = {"online": "tpot_p90_ms", "offline": "out_tok_s"}
        assert m["moves"] == e2e[name.rsplit(".", 1)[1]]
        for cell in m["workloads"]:
            assert name in {x["name"] for x in b.per_layer(cell)}


def test_the_anchor_puts_the_traced_steps_on_the_harness_clock():
    off = 7_000  # the trace's steps 7 µs after the harness's
    host = [("bench.step", LO + off, 400_000),
            ("bench.step", LO + 500_000 + off, 400_000)]
    assert phases.anchor_error_s(_run(host_steps=host)) == \
        pytest.approx(7e-6)
    assert phases.anchor_error_s(_run(trace=False)) is None
    assert phases.anchor_error_s(_run(host_steps=host[:1])) is None
