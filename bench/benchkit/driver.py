"""One run of one cell: set up, warm up, measure for the window, check,
print the result.

The window drives `ContinuousBatchingEngine.step` over `launch.serve.
ModelServer` with four weighted tenants and the fused admission kernel
(``use_kernel=True``), in bfloat16, with weights the benchmark makes on
the device from the seed.  The harness calls only the engine, its
``submit``, the server's ``prefill_request`` and ``step_fn`` (handed to
the engine inside the harness's spans) and its own ``sample_fn``.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path

import jax
import numpy as np

from . import record, schedule_ref, traffic
from .clock import CompileClock
from .model import check_tree, make_params, tree_paths
from .peaks import peaks
from .record import Call, ReqRecord, Run, Span
from .spec import Bench

TRACE_S = 5.0  # a traced run traces the last seconds of its window
WARM_BACKLOG = 256  # open loop: every backlog length the kernel's first
#                     bucket serves is warmed up (each is its own program)
# the reference's sample, in every cell: finished requests until there are
# at least this many, holding at least this many served tokens
SAMPLE_REQUESTS, SAMPLE_TOKENS = 8, 4096


class Fault(RuntimeError):
    """A run that cannot be measured: it prints no result."""


def process_age() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    ticks = os.sysconf("SC_CLK_TCK")
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19]) / ticks
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start


class Harness:
    """Spans and decode-call records around the program's callables."""

    def __init__(self, server, trace_on):
        self.server = server
        self.trace_on = trace_on  # () -> bool: is the profiler running
        self.spans: list[Span] = []
        self.calls: list[Call] = []
        self.n_calls = 0
        self.born: dict[int, int] = {}
        self.ring_high = 0

    def span(self, name):
        return (jax.profiler.TraceAnnotation("bench." + name)
                if self.trace_on() else contextlib.nullcontext())

    def _ring(self, rids):
        for rid in rids:
            self.ring_high = max(self.ring_high,
                                 self.n_calls - self.born[rid] + 1)

    def prefill_fn(self, req):
        self.born[req.rid] = self.n_calls
        t0 = time.perf_counter()
        with self.span("prefill"):
            self.server.prefill_request(req)
        t1 = time.perf_counter()
        n = len(req.prompt)
        self.spans.append(Span("prefill", t0, t1, n))
        dt = (t1 - t0) / max(n, 1)
        self.calls.extend(Call(t0 + i * dt, t0 + (i + 1) * dt, [i], "prefill")
                          for i in range(n))
        self.n_calls += n
        self.ring_high = max(self.ring_high, n)

    def step_fn(self, active):
        decoding = [r for r in active if r.out_tokens]
        t0 = time.perf_counter()
        with self.span("decode"):
            logits = self.server.step_fn(active)
        t1 = time.perf_counter()
        if decoding:
            self._ring([r.rid for r in decoding])
            self.n_calls += 1
            self.spans.append(Span("decode", t0, t1, len(decoding)))
            self.calls.append(Call(t0, t1, [len(r.prompt) + len(r.out_tokens)
                                            - 1 for r in decoding], "decode"))
        return logits

    def sample_fn(self, logits):
        t0 = time.perf_counter()
        with self.span("sample"):
            toks = np.asarray(logits).argmax(-1)
        self.spans.append(Span("sample", t0, time.perf_counter()))
        return toks


def drive(seconds: float, *, dues, send, idle, step, tick=None,
          clock=time.perf_counter, sleep=time.sleep):
    """The measured window.  Open loop (``dues``: seconds after the open at
    which each request is due): before each step, ``send(i, due)`` every
    request due by now, in order; while ``idle()``, sleep until the next
    is due.  Backlog (``dues`` None): ``send(None, None)`` tops the queue
    up before each step.  ``step()`` runs until the window has closed;
    the step that is running then finishes inside it.  Returns (open,
    close, lateness of each send)."""
    t_open = clock()
    t_end = t_open + seconds
    nxt, late = 0, []
    while True:
        now = clock()
        if now >= t_end:
            break
        if tick is not None:
            tick(now, t_open)
        if dues is None:
            send(None, None)
        else:
            while nxt < len(dues) and t_open + dues[nxt] <= now:
                due = t_open + dues[nxt]
                send(nxt, due)
                late.append(clock() - due)
                nxt += 1
            if idle():
                wake = t_end if nxt == len(dues) else t_open + dues[nxt]
                sleep(max(0.0, min(wake, t_end) - clock()))
                continue
        step()
    return t_open, clock(), late


def _warm_admission(tenants: dict, slots: int, sizes) -> None:
    """Load the admission round's programs for every backlog length in
    ``sizes``, on an engine of its own with no model: ``slots`` requests
    that never finish hold every slot, and the backlog grows by one per
    step."""
    from repro.serving.scheduler import ContinuousBatchingEngine, Request

    eng = ContinuousBatchingEngine(
        lambda act: np.zeros((len(act), 1), np.float32), lambda r: None,
        slots, tenants=tenants, use_kernel=True)
    names = list(tenants)
    rid = 0

    def add():
        nonlocal rid
        eng.submit(Request(rid=rid, prompt=[1], max_new_tokens=1 << 30,
                           tenant_id=names[rid % len(names)]))
        rid += 1

    for _ in range(slots):
        add()
    eng.step(lambda lg: lg.argmax(-1))
    for n in sizes:
        while rid - slots < n:
            add()
        eng.step(lambda lg: lg.argmax(-1))


def run_cell(bench: Bench, name: str, seed: int, seconds: float,
             trace: bool = False, control: bool = False, *,
             require_tpu: bool = True, compile_cache: bool = True,
             out=sys.stdout, err=sys.stderr) -> int:
    """Run one cell and print its result; returns the exit code."""
    import jax.numpy as jnp

    t_start, age_at_start = time.perf_counter(), process_age()
    cell = bench.cell(name)
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"bench: JAX found no accelerator: {e}", file=err)
        return 3
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu" or len(devs) < cell["chips"]):
        print(f"bench: cell {name} needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devs)} {dev.platform} device(s)", file=err)
        return 3
    chip_peaks = peaks(dev.device_kind) if require_tpu else {}

    from repro.launch.serve import ModelServer
    from repro.serving.scheduler import ContinuousBatchingEngine, Request

    if compile_cache:
        from repro.launch.compile_cache import setup_compile_cache

        setup_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock()
    mem = {}

    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    cfile = bench.cell_file(name)
    family = bench.family(cell["config"])
    spec = family.spec(cfg)
    slots, ring = int(cfg["slots"]), int(cfile["kv_ring"])
    tenants = traffic.weights(mix)
    tindex = {t: i for i, t in enumerate(tenants)}
    dtype = jnp.dtype(cfg["dtype"])

    # -- the program, with the benchmark's weights --------------------------
    def note(phase):
        """Device bytes in use and peak, and seconds since the process
        started, after each phase of set-up."""
        st = dev.memory_stats() or {}
        mem[phase] = [st.get("bytes_in_use"), st.get("peak_bytes_in_use"),
                      round(time.perf_counter() - t_start + age_at_start, 3)]

    server = ModelServer(family.program_config(cfg), slots, max_len=ring,
                         seed=0, dtype=dtype)
    note("server")
    served = tree_paths(server.params)
    server.params = None  # the program's own draw; freed before ours
    gc.collect()
    params = make_params(family.leaves(spec), seed, dtype)
    check_tree(tree_paths(params), served)
    server.params = params
    note("weights")

    tracing = [False]
    h = Harness(server, lambda: tracing[0])
    eng = ContinuousBatchingEngine(h.step_fn, h.prefill_fn, slots,
                                   tenants=tenants, use_kernel=True)
    rounds = [0]  # engine rounds so far (== the engine's own count)
    submits: list[tuple[int, int, int, int]] = []
    reqs: dict[int, Request] = {}

    def submit(rid, tenant, prompt, max_new):
        r = Request(rid=rid, prompt=prompt, max_new_tokens=max_new,
                    tenant_id=tenant)
        eng.submit(r)
        submits.append((rid, tindex[tenant], max_new, rounds[0]))
        reqs[rid] = r
        return r

    def step():
        eng.step(h.sample_fn)
        rounds[0] += 1

    # -- warm-up: every slot, the decode and row-reset programs --------------
    warm = [submit(-1 - i, list(tenants)[i % len(tenants)], [1, 2], 2)
            for i in range(slots)]
    while any(len(r.out_tokens) < 2 for r in warm):
        step()
    arrivals = mix["arrivals"]["kind"]
    note("warm_model")
    if arrivals == "poisson":
        _warm_admission(tenants, slots, range(1, WARM_BACKLOG + 1))

    plan = traffic.plan(mix, seed, seconds, spec.vocab)
    recs: dict[int, ReqRecord] = {}
    live: list[int] = []  # submitted, not yet finished
    backlog = [0]  # submitted, not yet admitted

    def send(p: traffic.Planned, due=None):
        r = submit(p.rid, p.tenant, p.prompt, p.max_new)
        recs[p.rid] = ReqRecord(p.rid, p.tenant, len(p.prompt), p.max_new,
                                due=due, submit=time.perf_counter())
        live.append(p.rid)
        backlog[0] += 1
        return r

    def stamp(t, in_window=True):
        done = []
        for rid in live:
            r, rec = reqs[rid], recs[rid]
            n = len(r.out_tokens)
            if n == rec.seen:
                continue
            if rec.seen == 0:
                backlog[0] -= 1
            if in_window:
                rec.first = t if rec.first is None else rec.first
                rec.last = t
                rec.n_window += n - rec.seen
                out_tokens[0] += n - rec.seen
            rec.seen = n
            if n >= r.max_new_tokens:
                done.append(rid)
        for rid in done:
            live.remove(rid)

    out_tokens = [0]
    stream = None
    depth = 0
    if arrivals == "backlog":
        depth = int(mix["arrivals"]["depth"])
        stream = traffic.backlog_stream(plan)
        first = [next(stream) for _ in range(depth)]
        rs = [Request(rid=p.rid, prompt=p.prompt, max_new_tokens=p.max_new,
                      tenant_id=p.tenant) for p in first]
        eng.submit_batch(rs)
        for p, r in zip(first, rs):
            submits.append((p.rid, tindex[p.tenant], p.max_new, rounds[0]))
            reqs[p.rid] = r
            recs[p.rid] = ReqRecord(p.rid, p.tenant, len(p.prompt), p.max_new)
            live.append(p.rid)
        backlog[0] = depth
        step()  # the first wave fills every slot before the window
        stamp(time.perf_counter(), in_window=False)

    note("ready")
    # -- the window ----------------------------------------------------------
    trace_dir = bench.root / ".bench_trace" / f"{name}-{os.getpid()}"
    gc.collect()
    c0, s0 = clock.count, len(h.spans)
    k0, r0 = len(h.calls), rounds[0]
    trace_at = seconds - min(TRACE_S, seconds / 2) if trace else None
    win_anno = []
    trace_host = []

    def tick(now, t_open):
        if trace_at is not None and not tracing[0] and now >= t_open + trace_at:
            jax.profiler.start_trace(str(trace_dir))
            tracing[0] = True
            win_anno.append(jax.profiler.TraceAnnotation("bench.window"))
            win_anno[0].__enter__()
            trace_host.append(time.perf_counter())

    def send_due(i, due):
        if i is None:
            while backlog[0] < depth:
                send(next(stream))
        else:
            send(plan[i], due=due)

    def timed_step():
        t0 = time.perf_counter()
        with h.span("step"):
            step()
        t1 = time.perf_counter()
        h.spans.append(Span("step", t0, t1))
        stamp(t1)

    t_open, t_close, late = drive(
        seconds, dues=[p.due for p in plan] if arrivals == "poisson" else None,
        send=send_due, idle=lambda: not live, step=timed_step, tick=tick)
    setup_s = t_open - t_start + age_at_start
    if win_anno:
        win_anno[0].__exit__(None, None, None)
    window_compiles = clock.count - c0
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    if tracing[0]:
        jax.profiler.stop_trace()
        tracing[0] = False

    print(f"window: {json.dumps({'seconds': t_close - t_open, 'setup_s': setup_s, 'steps': rounds[0] - r0, 'compiles_in_window': window_compiles, 'ring_high_water': h.ring_high, 'kv_ring': ring, 'waiting_at_close': backlog[0], 'in_flight_at_close': len(live), 'setup': mem, 'memory_peak_bytes': peak})}",
          file=err)
    if late:
        print(f"generator lateness (due to submit): "
              f"{json.dumps({'requests': len(late), 'median_s': float(np.median(late)), 'p90_s': record.percentile(late, 90), 'max_s': max(late)})}",
              file=err)
    if window_compiles:
        raise Fault(f"{window_compiles} program(s) compiled inside the "
                    f"measured window: a shape was not warmed up")

    # -- the check: schedule and admission, then the model -------------------
    want = schedule_ref.simulate(list(tenants.values()), slots, rounds[0],
                                 submits)
    sched_bad = [rid for rid, (a, n) in want.items()
                 if (reqs[rid].admit_round, len(reqs[rid].out_tokens)) != (a, n)]
    admit_bad = [rid for rid, (a, _) in want.items()
                 if reqs[rid].admit_round != a]
    finished = [recs[rid] for rid in recs
                if recs[rid].n_window and recs[rid].seen == recs[rid].max_new]
    # the sample: the longest finished request, then others drawn from
    # the seed, until it holds SAMPLE_REQUESTS and SAMPLE_TOKENS (or every
    # finished request)
    rng = np.random.default_rng(np.random.SeedSequence([seed & (2**64 - 1), 3]))
    finished.sort(key=lambda r: (-r.max_new, r.rid))
    order = finished[:1] + [finished[1:][i]
                            for i in rng.permutation(max(len(finished) - 1, 0))]
    sample, ntok = [], 0
    for rec in order:
        if len(sample) >= SAMPLE_REQUESTS and ntok >= SAMPLE_TOKENS:
            break
        served = list(reqs[rec.rid].out_tokens)
        sample.append((reqs[rec.rid].prompt, served))
        ntok += len(served)
    if not sample:
        raise Fault("no request finished in the window: the reference has "
                    "nothing to compare")

    run = Run(cell=name, config=cfg, spec=spec, peaks=chip_peaks,
              t_open=t_open, t_close=t_close, setup_s=setup_s,
              requests=list(recs.values()), spans=h.spans[s0:],
              calls=h.calls[k0:], steps=rounds[0] - r0,
              out_tokens=out_tokens[0],
              trace_host=((trace_host or [t_open])[0], t_close),
              family=family)
    attempted = sum(1 for r in recs.values()
                    if (r.due is not None and t_open <= r.due < t_close)
                    or r.n_window)
    del eng, server, params, h.server, warm
    gc.collect()

    t_ref = time.perf_counter()
    gaps, ctl = family.served_gaps(spec, seed, sample, control=control)
    t_ref = time.perf_counter() - t_ref
    limits = cfile.get("limits", {})
    detail = {"sample_requests": len(sample), "sample_tokens": int(len(gaps)),
              "program_gap_mean": float(gaps.mean()) if len(gaps) else None,
              "gap_max": float(gaps.max()) if len(gaps) else None,
              "gap_flip_share": float((gaps > 0).mean()) if len(gaps) else None,
              "reference_s": t_ref}
    if ctl is not None:
        # the control: the int8 reference's tokens stand in the program's
        # place, and the same comparison judges them
        gaps = ctl
        detail.update(control_gap_mean=float(ctl.mean()),
                      control_gap_max=float(ctl.max()),
                      control_flip_share=float((ctl > 0).mean()))
    print(f"reference: {json.dumps(detail)}", file=err)
    # the compared number is the mean gap: the widest gap reads as far for
    # bf16 as for the int8 control (both are bounded by the spacing of the
    # top logits), the mean separates them (PERF.md, section 2)
    mean_gap = float(gaps.mean()) if len(gaps) else float("inf")
    checks = {
        "logit_gap_mean": (mean_gap, limits.get("logit_gap_mean")),
        "admission_mismatch": (len(admit_bad), 0),
        "schedule_mismatch": (len(sched_bad), 0),
    }
    correct = all(lim is not None and v <= lim for v, lim in checks.values())

    # -- metrics -------------------------------------------------------------
    if trace:
        from . import trace as tr

        pb = sorted(trace_dir.rglob("*.xplane.pb"))
        run.trace = tr.load(str(pb[-1]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace: offset {run.trace.offset} ns, "
              f"{len(run.trace.ops)} device ops, "
              f"{len(run.trace.programs)} programs", file=err)
    entries = bench.per_layer(name) if trace else bench.end_to_end(name)
    metrics = {}
    for m in entries:
        v = bench.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": len(sched_bad), "metrics": metrics, "device": device}
    if trace:
        from . import trace as tr

        device["busy_s"] = tr.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(run.trace),
                               "idle_gaps": tr.idle_by_activity(run.trace)[:10]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return 0
