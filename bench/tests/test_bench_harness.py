"""The benchmark's own tests, on the CPU at tiny sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They check that cells, configurations, traffic and metrics are found by
name, a second architecture's family among them; the tails and the
open-loop timing; the operation and byte counts against a hand count, and
which calls the decode roofline counts; that the weights are the same bit
for bit as before the families moved out of the harness; the trace
reduction on a small recorded trace; the schedule reference against the
engine; that a run refuses to run off the TPU; and that a whole run at a
tiny size comes out correct, and comes out not correct with the timed
path broken underneath it.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from benchkit import record, schedule_ref, trace, traffic  # noqa: E402
from benchkit.driver import Fault, drive, run_cell  # noqa: E402
from benchkit.spec import Bench  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _doc():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _qwen2():
    """The Qwen2 family module (every configuration of the benchmark)."""
    return Bench(ROOT).family("qwen2-0.5b")


# ------------------------------------------------------------- by name ----


def test_every_entry_is_found_by_name():
    b = Bench(ROOT)
    for cell in b.doc["workloads"]:
        cfg = b.config(cell["config"])
        assert cfg["name"] == cell["config"]
        fam = b.family(cell["config"])
        assert fam.spec(cfg).vocab == cfg["vocab_size"]
        assert fam.program_config(cfg).vocab == cfg["vocab_size"]
        mix = b.traffic(cell["traffic"])
        assert mix["arrivals"]["kind"] in ("poisson", "backlog")
        assert b.cell_file(cell["name"])["kv_ring"] > 0
        e2e = {m["name"] for m in b.end_to_end(cell["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        per = b.per_layer(cell["name"])
        assert per and all(m["moves"] in e2e for m in per)
    for m in b.doc["end_to_end"] + b.doc["per_layer"]:
        assert callable(b.reader(m["name"]))


def test_benchmark_json_keeps_to_its_schema():
    doc = _doc()
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    R = doc["run_seconds"]
    assert (2 + 14 * 24) * (R + 60) + 24 * 2 * 90 + 1200 <= 43200
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in doc[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for m in doc["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    layers = {m["layer"] for m in doc["per_layer"]}
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in doc["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert all(len(layer) <= 200 and "\n" not in layer for layer in layers)
    for c in doc["workloads"]:
        assert len(c["why"]) <= 200 and c["chips"] in (1, 4)
        assert (BENCH / "cells" / f"{c['name']}.json").is_file()
    for c in doc["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")


def _copy_bench(dst: Path) -> Path:
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    return dst


def test_a_cell_added_by_files_and_entries_alone(tmp_path):
    root = _copy_bench(tmp_path)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    cfg["name"] = "extra-model"
    (root / "bench/configs/extra-model.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix["arrivals"]["rate_rps"] = 0.5
    (root / "bench/traffic/extra-mix.json").write_text(json.dumps(mix))
    (root / "bench/cells/extra-model.extra-mix.json").write_text(
        json.dumps({"kv_ring": 1024, "limits": {"logit_gap_mean": 1.0}}))
    (root / "bench/metrics/extra_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    doc["configs"].append({"name": "extra-model", "source": "x",
                           "file": "bench/configs/extra-model.json",
                           "reduced": [], "why": "x"})
    doc["workloads"].append({"name": "extra-model.extra-mix",
                             "config": "extra-model", "traffic": "extra-mix",
                             "chips": 1, "why": "x"})
    doc["end_to_end"][0]["workloads"].append("extra-model.extra-mix")
    doc["per_layer"].append({"name": "extra_metric", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "x", "moves": doc["end_to_end"][0]["name"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    b = Bench(root)
    assert b.config("extra-model")["name"] == "extra-model"
    assert b.family("extra-model").__file__.endswith(
        "families/Qwen2ForCausalLM.py")
    assert b.traffic("extra-mix")["arrivals"]["rate_rps"] == 0.5
    assert b.cell_file("extra-model.extra-mix")["kv_ring"] == 1024
    assert "extra_metric" in {m["name"]
                              for m in b.per_layer("extra-model.extra-mix")}
    assert b.reader("extra_metric")(None) == 42.0


SECOND_FAMILY = "PrefixDecoderForCausalLM"


def _benchkit_files(root: Path) -> dict[str, bytes]:
    return {str(f.relative_to(root)): f.read_bytes()
            for f in sorted((root / "bench/benchkit").rglob("*.py"))}


def test_a_family_added_by_files_and_entries_alone(tmp_path):
    """A configuration of another architecture: its family module, its
    configuration naming it, a traffic mix and a cell file, and their
    entries; a tiny run through that family's program config, weights
    and reference comes out correct, and nothing of the harness
    changes."""
    root = _copy_bench(tmp_path)
    kit = _benchkit_files(root)
    shutil.copy(BENCH / "tests" / "data" / f"{SECOND_FAMILY}.py",
                root / "bench" / "families" / f"{SECOND_FAMILY}.py")
    cfg = dict(_cfg("qwen2-0.5b"), **TINY)
    cfg.update(name="second", architectures=[SECOND_FAMILY],
               num_hidden_layers=3)
    (root / "bench/configs/second.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny.json").write_text(json.dumps(_tiny_mix()))
    (root / "bench/cells/second.tiny.json").write_text(json.dumps(
        {"kv_ring": 2048, "limits": {"logit_gap_mean": TINY_LIMIT}}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "second", "source": "test",
                           "file": "bench/configs/second.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "second.tiny", "config": "second",
                             "traffic": "tiny", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    b = Bench(root)
    fam = b.family("second")
    assert fam.__file__.endswith(f"families/{SECOND_FAMILY}.py")
    program = fam.program_config(cfg)
    assert len(program.prefix) == 1 and not program.qkv_bias
    res, _ = _tiny_run(root, "second.tiny")
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert _benchkit_files(root) == kit


# ------------------------------------------------------ tails and time ----


def _run(reqs, t_open=0.0, t_close=10.0):
    return record.Run(cell="c", config={}, spec=None, peaks={},
                      t_open=t_open, t_close=t_close, setup_s=1.0,
                      requests=reqs, spans=[], calls=[], steps=0,
                      out_tokens=0)


def test_tails_count_every_request_and_censor_the_waiting():
    R = record.ReqRecord
    reqs = [R(i, "t", 1, 4, due=float(i), first=float(i) + 0.5)
            for i in range(9)]
    reqs.append(R(9, "t", 1, 4, due=8.0))  # still waiting at the close
    reqs.append(R(10, "t", 1, 4, due=11.0))  # due after the close
    reqs.append(R(11, "t", 1, 4, due=-1.0, first=0.5))  # due before
    got = sorted(record.ttft_samples(_run(reqs)))
    assert got == [0.5] * 9 + [2.0]
    assert record.percentile(got, 90) == pytest.approx(0.5 + 0.1 * 1.5)
    assert record.percentile([], 90) is None
    reqs = [R(0, "t", 1, 9, first=1.0, last=3.0, n_window=5),
            R(1, "t", 1, 9, first=1.0, last=1.0, n_window=1)]
    assert record.tpot_samples(_run(reqs)) == [0.5]


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def test_open_loop_sends_on_due_time_and_times_from_it():
    clk = FakeClock()
    dues = [0.0, 0.5, 0.6, 3.0]
    sent, first = {}, {}
    inflight = []

    def send(i, due):
        sent[i] = (due, clk())
        inflight.append(i)

    def step():  # every step takes 0.3 s and serves all in flight
        clk.t += 0.3
        for i in inflight:
            first.setdefault(i, clk())
        inflight.clear()

    t_open, t_close, late = drive(4.0, dues=dues, send=send,
                                  idle=lambda: not inflight, step=step,
                                  clock=clk, sleep=clk.sleep)
    assert t_open == 100.0 and t_close >= 104.0
    # request 2 waits for the step that started before it was due
    assert [round(x, 9) for x in late] == [0.0, 0.0, 0.2, 0.0]
    ttft = {i: first[i] - (t_open + dues[i]) for i in first}
    assert ttft[0] == pytest.approx(0.3) and ttft[2] == pytest.approx(0.5)
    assert ttft[3] == pytest.approx(0.3)


def test_every_seed_gets_the_same_work():
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    a = traffic.plan(mix, 2**40 + 3, 50, 1000)
    b = traffic.plan(mix, 7, 50, 1000)
    assert len(a) == len(b) == round(mix["arrivals"]["rate_rps"] * 50)
    gaps = lambda plan: list(np.diff([p.due for p in plan]))  # noqa: E731
    assert gaps(a) == gaps(b) and sorted(gaps(a)) != gaps(a)
    for key in (lambda p: len(p.prompt), lambda p: p.max_new,
                lambda p: p.tenant):
        assert list(map(key, a)) == list(map(key, b))
        assert sorted(map(key, a)) != list(map(key, a))  # not sorted
    assert [p.prompt for p in a] != [p.prompt for p in b]
    assert all(0 <= p.due < 50 for p in a) and a[0].due == 0.0
    n = len(a)
    for t, v in mix["tenants"].items():
        assert abs(sum(p.tenant == t for p in a) - v["share"] * n) < 1
    again = traffic.plan(mix, 2**40 + 3, 50, 1000)
    assert [p.prompt for p in again] == [p.prompt for p in a]
    other = traffic.plan(dict(mix, layout_seed=1), 7, 50, 1000)
    assert [p.max_new for p in other] != [p.max_new for p in b]


# ------------------------------------------------------------ counting ----


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _spec(name):
    return _qwen2().spec(_cfg(name))


def _cost(spec, contexts):
    return _qwen2().call_cost(spec, record.Call(0.0, 1.0, contexts, "decode"))


def test_decode_cost_hand_count_qwen2():
    s = _spec("qwen2-0.5b")
    assert s.params() == 494_032_768
    # one row at position 99 and one at position 0
    flops, nbytes = _cost(s, [99, 0])
    layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    head = 151_936 * 896
    per_row = 2 * (24 * layer + head)
    attn = 4 * 24 * 14 * 64 * (100 + 1)
    assert flops == 2 * per_row + attn
    weights = 494_032_768 * 2  # tied: the embedding is the head, read once
    kv = 24 * 2 * 2 * 64 * 2  # 12,288 B per token
    assert kv == 12_288
    assert nbytes == weights + kv * (99 + 0 + 2) + 2 * 151_936 * 4


def test_decode_cost_hand_count_codeqwen_8_layers():
    s = _spec("codeqwen1.5-7b")
    layer_params = (4096 * (32 + 8) * 128 + 32 * 128 * 4096
                    + 3 * 4096 * 13_440 + (32 + 8) * 128 + 2 * 4096)
    assert s.params() == 8 * layer_params + 4096 + 2 * 92_416 * 4096
    assert s.params() == 2_380_378_112
    flops, nbytes = _cost(s, [10] * 8)
    weights = (8 * layer_params + 4096 + 92_416 * 4096) * 2 + 8 * 4096 * 2
    kv = 8 * 2 * 4 * 128 * 2
    assert nbytes == weights + kv * (8 * 10 + 8) + 8 * 92_416 * 4
    mm = 8 * (4096 * 40 * 128 + 4096 * 4096 + 3 * 4096 * 13_440)
    assert flops == 8 * 2 * (mm + 92_416 * 4096) + 8 * 4 * 8 * 32 * 128 * 11
    assert _cost(s, []) == (0.0, 0.0)


def test_a_key_the_program_cannot_state_is_refused():
    """codeqwen1.5-7b publishes RMSNorm eps 1e-5; the program's norms take
    1e-6 and `ModelConfig` has no field for it."""
    fam = _qwen2()
    with pytest.raises(ValueError, match="rms_norm_eps"):
        fam.program_config(_cfg("codeqwen1.5-7b"))
    for key, value in [("hidden_act", "gelu"), ("use_sliding_window", True),
                       ("rope_scaling", {"type": "yarn", "factor": 4.0})]:
        with pytest.raises(ValueError, match=key):
            fam.program_config(dict(_cfg("qwen2-0.5b"), **{key: value}))
    for name in ("qwen2-0.5b", "qwen2-7b"):
        assert fam.program_config(_cfg(name)).num_units == _cfg(name)[
            "num_hidden_layers"]


def test_decode_roofline_counts_decode_calls_only():
    """1 MB a row at 1 GB/s is 1 ms of least time per row; two decode
    calls of 2 and 4 rows against two 10-ms `_decode_rows` programs read
    (2 + 4) / 20 = 30%, whatever prompt tokens ran beside them."""
    class Family:
        @staticmethod
        def call_cost(spec, call):
            return 0.0, 1e6 * len(call.contexts)

    decode = [record.Call(1.0, 1.1, [5, 6], "decode"),
              record.Call(2.0, 2.1, [5, 6, 7, 8], "decode")]
    prompt = [record.Call(1.2 + i / 100, 1.21 + i / 100, [i], "prefill")
              for i in range(50)]
    ms = 10**6
    programs = [("jit__decode_rows(1)", 100 * ms, 10 * ms),
                ("jit__prefill_chunk(2)", 300 * ms, 10 * ms),
                ("jit__decode_rows(3)", 500 * ms, 10 * ms)]
    t = trace.DeviceTrace(lo=0, hi=1000 * ms, programs=programs)
    reader = Bench(ROOT).reader("decode_roofline")
    for calls in (decode, decode + prompt, prompt[:10] + decode):
        run = _run([])
        run.calls, run.trace, run.trace_host = calls, t, (0.0, 10.0)
        run.peaks = {"hbm_bytes_s": 1e9, "bf16_flops": 1e12}
        run.family = Family
        assert reader(run) == pytest.approx(30.0)
    run.calls = prompt
    assert reader(run) is None


# --------------------------------------------------------------- trace ----


def test_trace_reduction_on_a_recorded_trace():
    t = trace.DeviceTrace.from_json(
        (BENCH / "tests" / "data" / "trace_excerpt.json").read_text())
    busy = trace.busy_s(t)
    assert 0 < busy <= t.window_s
    iv = trace.busy_intervals(t)
    assert all(a < b <= c for (a, b), (c, _) in zip(iv, iv[1:]))
    gaps = trace.idle_gaps(t)
    assert sum(b - a for a, b in gaps) * trace.NS == pytest.approx(
        t.window_s - busy, rel=1e-9)
    idle = trace.idle_by_activity(t)
    assert sum(s for _, s in idle) == pytest.approx(t.window_s - busy,
                                                    rel=1e-9)
    secs, n = trace.program_time(t, "_decode_rows")
    # a program's span holds its ops and a little launch time around them
    assert n == 2 and busy * 0.99 < secs < t.window_s
    top = trace.top_ops(t)
    assert 0 < len(top) <= 10 and top[0][0].startswith("jit_")
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)


def test_clock_offset_puts_runs_inside_their_spans():
    # runs of 3 units every 10 units on the device clock; the host's spans
    # (issue + await) and dispatches are 1000 units later
    runs = [(10 * k, 10 * k + 3) for k in range(40)]
    spans = [(10 * k + 999, 10 * k + 1005) for k in range(40)]
    sent = [10 * k + 999 for k in range(40)]
    assert trace.clock_offset(sent, runs, spans) == 999  # at the dispatch
    # the host tracer caught 25 more dispatches before the device's began
    early = [-10 * k for k in range(25, 0, -1)]
    assert trace.clock_offset(early + sent, runs, spans) == 999
    assert trace.clock_offset([], runs, spans) == 0


def test_trace_reduction_by_hand():
    t = trace.DeviceTrace(
        lo=0, hi=100,
        ops=[("%a = f()", 10, 20), ("%b = g()", 20, 20), ("%c = h()", 90, 20),
             ("%in = k()", 12, 5)],
        programs=[("jit_p(1)", 10, 30), ("jit_q(2)", 90, 20)],
        host=[("bench.step", 0, 100), ("bench.decode", 5, 40),
              ("bench.sample", 50, 10)])
    assert trace.busy_intervals(t) == [(10, 40), (90, 100)]
    assert trace.busy_s(t) == pytest.approx(40e-9)
    assert trace.idle_gaps(t) == [(0, 10), (40, 90)]
    idle = dict(trace.idle_by_activity(t))
    assert idle["decode"] == pytest.approx(10e-9)  # 0-5 step, 5-10 decode
    assert idle["sample"] == pytest.approx(10e-9)
    assert idle["step"] == pytest.approx(40e-9)
    assert trace.program_time(t, "jit_q") == (pytest.approx(10e-9), 1)
    top = dict(trace.top_ops(t))
    # less the op inside it and the part of b that overlaps it
    assert top["jit_p/a"] == pytest.approx(5e-9)
    assert top["jit_p/b"] == pytest.approx(20e-9)
    assert top["jit_p/in"] == pytest.approx(5e-9)
    assert top["jit_q/c"] == pytest.approx(10e-9)  # clipped to the window


# ---------------------------------------------------------- off the TPU ----


def test_run_exits_nonzero_off_the_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2-0.5b.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()
    assert "needs 1 TPU" in p.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    root = _copy_bench(tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2-0.5b.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=root, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and not p.stdout.strip()


# --------------------------------------------- references at tiny sizes ----


def test_schedule_reference_follows_the_engine():
    from repro.serving.scheduler import ContinuousBatchingEngine, Request

    weights = {"a": 4.0, "b": 2.0, "c": 1.0, "d": 1.0}
    for seed in range(3):
        rng = np.random.default_rng(seed)
        eng = ContinuousBatchingEngine(
            lambda act: np.zeros((len(act), 2), np.float32), lambda r: None,
            4, tenants=weights, use_kernel=True)
        reqs, submits = [], []
        for rnd in range(40):
            for _ in range(rng.poisson(0.8)):
                t = int(rng.choice(4, p=[0.55, 0.25, 0.12, 0.08]))
                r = Request(rid=len(reqs), prompt=[1],
                            max_new_tokens=int(rng.integers(1, 9)),
                            tenant_id="abcd"[t])
                eng.submit(r)
                reqs.append(r)
                submits.append((r.rid, t, r.max_new_tokens, rnd))
            eng.step(lambda lg: lg.argmax(-1))
        want = schedule_ref.simulate(list(weights.values()), 4, 40, submits)
        got = {r.rid: (r.admit_round, len(r.out_tokens)) for r in reqs}
        assert got == want


def _same_draw(a, b):
    """Equal but for a rare last-bit difference: XLA may round the f32
    draw differently when it fuses it into a larger program."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    diff = a != b
    assert diff.mean() < 1e-4
    assert np.all(np.abs(a - b)[diff] <= np.abs(a)[diff] * 2.0 ** -7)


def test_weights_are_drawn_again_by_the_reference():
    import jax
    import jax.numpy as jnp

    from benchkit.model import layer_leaf, make_params, seed_key

    spec = _tiny_spec()
    leaves = _qwen2().leaves(spec)
    params = make_params(leaves, 2**45 + 11, jnp.bfloat16)
    key = seed_key(2**45 + 11)
    draw = jax.jit(layer_leaf, static_argnums=(1, 2, 4))
    for path, leaf in leaves.items():
        node = params
        for p in path.split("/"):
            node = node[p]
        for layer in range(leaf.stacked or 1):
            again = draw(key, path, leaf, layer, jnp.bfloat16)
            _same_draw(node[layer] if leaf.stacked else node, again)
    other = make_params(leaves, 2**45 + 11 + 2**32, jnp.bfloat16)
    assert not np.array_equal(np.asarray(other["embed"]),
                              np.asarray(params["embed"]))


def _digest(tree) -> str:
    import jax

    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for kp, x in sorted(flat, key=lambda t: jax.tree_util.keystr(t[0])):
        h.update(jax.tree_util.keystr(kp).encode())
        h.update(np.asarray(x).view(np.uint16).tobytes())
    return h.hexdigest()


def test_the_qwen2_family_draws_the_weights_it_drew_before():
    """The digest of every bf16 bit of the tiny model's weights, as the
    harness drew them before the Qwen2 family moved out of it."""
    import jax.numpy as jnp

    from benchkit.model import make_params

    params = make_params(_qwen2().leaves(_tiny_spec()), 2**45 + 11,
                         jnp.bfloat16)
    assert _digest(params) == (
        "4980557157612656974809e35eec4d2acb02b2647151ac7db52932fc48efaf30")


def test_numbered_path_parts_make_lists():
    import jax.numpy as jnp

    from benchkit.model import Leaf, make_params, tree_paths

    params = make_params({"prefix/0/w": Leaf((2, 3), "matrix", 2),
                          "prefix/1/w": Leaf((3,), "norm"),
                          "units/b0/w": Leaf((4,), "bias", stacked=5)},
                         7, jnp.float32)
    assert isinstance(params["prefix"], list) and len(params["prefix"]) == 2
    assert tree_paths(params) == {"prefix/0/w": ((2, 3), "float32"),
                                  "prefix/1/w": ((3,), "float32"),
                                  "units/b0/w": ((5, 4), "float32")}


TINY = dict(name="tiny", hidden_size=256, intermediate_size=512,
            num_attention_heads=4, num_key_value_heads=2,
            num_hidden_layers=4, vocab_size=4096, slots=4)


# the tiny cell's limit on the mean gap: the program reads 0.00002 to
# 0.00021 on 512 tokens it served to the end (six seeds) and up to
# 0.00036 in a 4-s window, the int8 control 0.0013 to 0.0029 on the
# same 512 tokens, the planted faults far more
TINY_LIMIT = 0.0008
TINY_SECONDS = 4.0


def _tiny_spec():
    cfg = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    cfg.update(TINY)
    return _qwen2().spec(cfg)


def _tiny_mix():
    """A 16-deep backlog of short requests."""
    mix = json.loads((BENCH / "traffic" / "codegen.json").read_text())
    mix["arrivals"].update(depth=16, pool=64)
    mix["prompt"] = {"median": 8, "sigma": 0.5, "min": 2, "max": 24}
    mix["output"] = {"median": 64, "sigma": 0.5, "min": 32, "max": 128}
    return mix


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout's bench with one tiny cell: a 2-layer Qwen2-style model
    over 4 slots, a 16-deep backlog of short requests."""
    root = _copy_bench(tmp_path_factory.mktemp("tiny"))
    cfg = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    cfg.update(TINY)
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny.json").write_text(json.dumps(_tiny_mix()))
    (root / "bench/cells/tiny.tiny.json").write_text(json.dumps(
        {"kv_ring": 2048, "limits": {"logit_gap_mean": TINY_LIMIT}}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "tiny", "source": "test",
                           "file": "bench/configs/tiny.json", "reduced": [],
                           "why": "test"})
    doc["workloads"].append({"name": "tiny.tiny", "config": "tiny",
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "qwen2-0.5b.reason" in m.get("workloads", ()):
            m["workloads"].append("tiny.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def _tiny_run(root, cell="tiny.tiny", seed=2**33 + 5, control=False):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(Bench(root), cell, seed, TINY_SECONDS, control=control,
                  require_tpu=False, compile_cache=False, out=out, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(res)[-1] == "checks"
    assert err.getvalue().strip().splitlines()[-1].startswith("check ")
    return res, err.getvalue()


def test_a_tiny_run_is_correct(tiny_root):
    res, _ = _tiny_run(tiny_root)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"out_tok_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"


def test_a_window_that_finishes_nothing_is_a_fault(tiny_root):
    """Outputs of 32 or more tokens cannot finish in a 1-ms window: the
    run cannot be judged and prints no result."""
    out, err = io.StringIO(), io.StringIO()
    with pytest.raises(Fault, match="no request finished"):
        run_cell(Bench(tiny_root), "tiny.tiny", 7, 0.001, require_tpu=False,
                 compile_cache=False, out=out, err=err)
    assert not out.getvalue()


def _served_to_the_end(seed, n=4, prompt_len=16, new=128):
    """Prompts and the tokens the program serves them, every request run
    to its end through the engine (no window, so no timing in it)."""
    import jax.numpy as jnp

    from benchkit.model import make_params
    from repro.launch.serve import ModelServer
    from repro.serving.scheduler import ContinuousBatchingEngine, Request

    cfg = json.loads((BENCH / "configs" / "qwen2-0.5b.json").read_text())
    cfg.update(TINY)
    fam = _qwen2()
    server = ModelServer(fam.program_config(cfg), n, max_len=1024,
                         dtype=jnp.bfloat16)
    server.params = make_params(fam.leaves(_tiny_spec()), seed, jnp.bfloat16)
    eng = ContinuousBatchingEngine(server.step_fn, server.prefill_request,
                                   n, tenants={"a": 1.0}, use_kernel=True)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=[int(t) for t in rng.integers(
        1, TINY["vocab_size"], prompt_len)], max_new_tokens=new,
        tenant_id="a") for i in range(n)]
    eng.submit_batch(reqs)
    while any(len(r.out_tokens) < new for r in reqs):
        eng.step(lambda lg: np.asarray(lg).argmax(-1))
    return [(r.prompt, r.out_tokens) for r in reqs]


def test_the_control_in_the_programs_place_is_not_correct(tiny_root):
    res, err = _tiny_run(tiny_root, control=True)
    assert res["correct"] is False
    assert res["checks"]["logit_gap_mean"]["value"] > TINY_LIMIT
    assert res["checks"]["schedule_mismatch"]["value"] == 0
    detail = json.loads(err.split("reference: ", 1)[1].splitlines()[0])
    assert detail["program_gap_mean"] < TINY_LIMIT
    assert detail["control_gap_mean"] == res["checks"]["logit_gap_mean"][
        "value"]


def test_the_int8_control_fails_the_tiny_limit():
    served_gaps = _qwen2().served_gaps
    for seed in (30, 31, 32):
        sound, ctl = served_gaps(_tiny_spec(), seed, _served_to_the_end(seed),
                                 control=True)
        assert sound.mean() < TINY_LIMIT < ctl.mean()


def _broken(monkeypatch, how):
    from repro.launch.serve import ModelServer

    orig_call, orig_step = ModelServer._call, ModelServer.step_fn
    if how == "state":  # a call that returns its state unchanged
        def call(self, rows):
            old = self.caches
            out = orig_call(self, rows)
            self.caches = old
            return out
        monkeypatch.setattr(ModelServer, "_call", call)
    elif how == "half":  # half of the batch left out of each decode call
        def step_fn(self, active):
            out = orig_step(self, active)
            for i, r in enumerate(active):
                if i % 2 and r.out_tokens:
                    out[i] = r._last_logits
            return out
        monkeypatch.setattr(ModelServer, "step_fn", step_fn)
    elif how == "token":  # a token altered where it is produced
        def step_fn(self, active):
            out = np.array(orig_step(self, active))
            out[0] = np.roll(out[0], 1)
            return out
        monkeypatch.setattr(ModelServer, "step_fn", step_fn)


@pytest.mark.parametrize("how", ["state", "half", "token"])
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, how):
    _broken(monkeypatch, how)
    res, _ = _tiny_run(tiny_root)
    assert res["correct"] is False
    assert res["checks"]["logit_gap_mean"]["value"] > TINY_LIMIT or any(
        res["checks"][k]["value"] for k in ("admission_mismatch",
                                            "schedule_mismatch"))

