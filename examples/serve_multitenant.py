"""Multi-tenant QoS serving example: three tenants with 4:2:1 weights share
one continuous-batching engine, demonstrating

  * weighted-FCFS admission — under saturation, admission shares track the
    configured weights (stride-scheduled grant replenishment over per-tenant
    functional TWA semaphores);
  * the tombstone protocol — a batch of requests carrying an admission
    deadline that passes while queued is expired (tickets tombstoned) and
    never blocks later live requests;
  * the waiting-array effect at tenant granularity — the scheduler
    re-examines only tenant queues whose buckets were poked (skip ratio
    printed).

Run:  PYTHONPATH=src python examples/serve_multitenant.py [--kernel]
                                                          [--megastep]
                                                          [--paged]
                                                          [--shared-prefix]
                                                          [--chaos [seed]]
                                                          [--cluster [seed]]
                                                          [--trace]

Prefix caching (``--shared-prefix``): the three tenants serve
retry/regenerate traffic over one long shared system prompt through a
refcounted block pool with a weak prefix index (``prefix_cache=E``):
after the first wave prefills and registers, later admissions attach the
covered blocks by incref — zero prefill flops, zero new HBM — and
verbatim full-prompt repeats skip prefill entirely (``prefix_hits``);
divergence inside a shared tail block triggers a copy-on-write take
(``cow_copies``).  The run prints the sharing gauges and proves the
refcounted conservation identity drains back to a full pool; with
``--trace`` the per-round ``blocks_shared`` gauge and the accumulated
prefix counters land in the SLO table footer.

Cluster fabric (``--cluster [seed]``): four replica engines behind
`repro.serving.router.ReplicaRouter` — per-replica in-flight capacity as
a cluster-level TWA lease, a heartbeat reaper, exactly-once request
migration off dead replicas (warm takeover from the last checkpoint
snapshot where available), a per-replica circuit breaker — driven
through a seeded cluster FaultPlan (replica kill mid-megastep, KV
partition with zombie fencing, straggler, leaked lease) and verified
bit-identical against a fault-free twin.

Self-healing (``--chaos [seed]``): drives a chunked block-paged engine
through a seeded `repro.resilience.FaultPlan` (dropped pokes, counter
corruption, wedged slots, one mid-run crash) under the recovery ladder —
watchdog quarantine + backoff requeue, block-table audit, snapshot
restore + deterministic replay — printing every ladder action and the
exit conservation audit.  See src/repro/resilience/README.md.

Observability (``--trace``): attaches a `repro.obs.EngineObs` with a
streaming `JsonlSink` — every engine round (host ``step()`` or megastep
ring drain, identical records either way) appends one JSON line to
``trace_multitenant.jsonl`` with the per-round gauges and the TWA
waiting-array probes (bucket-occupancy histogram, per-tenant credit,
poke-window slack), and resolved requests feed per-tenant TTFT/TPOT
distributions.  At exit the rendered SLO-attainment table prints.
Attaching the observer adds zero host syncs (see src/repro/obs/README.md).

``--kernel`` (or ``ContinuousBatchingEngine(..., use_kernel=True)``) routes
the whole tenant round — expire → weighted replenish → FCFS admit →
reclaim — through the fused Pallas pass (`kernels.qos_admission`,
interpret mode off-TPU) instead of the host queue walk: same admission
semantics (bit-exact vs `functional_qos.qos_round`), one vectorized
in-graph sweep per engine step.

Device-resident engine (``--megastep``): the whole engine LOOP moves
in-graph — ``eng.megastep(K)`` runs K fused rounds (deadline preemption →
QoS admission → TWA slot assignment → decode+sample → completion) as one
jitted `lax.scan` over a donated on-device EngineState pytree
(`serving.engine_state`), so the host syncs once per K decoded tokens
instead of once per token.  Semantics are property-tested identical to K
sequential ``step()`` calls (tests/test_megastep.py).  Custom in-graph
models plug in via ``token_fn``/``admit_fn`` — see
`engine_state.paged_pool_token_fn` for paged decode attention over the
block pool with in-graph prompt prefill.

Continuous chunked prefill (``chunked_prefill=(chunk, budget)``): long
prompts stream through the engine in per-round chunks with INCREMENTAL
block allocation (admission on first-chunk demand, waiting-array parks on
pool exhaustion) — see examples/serve_longprompt.py for the dedicated
demo and serving/engine_state.py for the stall/park policy.

Block-paged KV pool (``--paged``): the engine additionally owns a shared
pool of KV blocks behind a TWA **block** semaphore
(``kv_pool=(num_blocks, block_size)``): admission gates on BOTH a free
slot and each request's worst-case block demand in strict FCFS order
(multi-resource admission), decode attention streams only the blocks a
sequence actually holds (`engine_state.paged_pool_token_fn`;
`kernels/paged_decode` on TPU), preemption/completion post the blocks
back, and `telemetry()` exposes the kv_blocks_free / kv_blocks_live
gauges.
"""

import sys
import time

import numpy as np

from repro.serving.scheduler import ContinuousBatchingEngine, Request

WEIGHTS = {"gold": 4.0, "silver": 2.0, "bronze": 1.0}


def _make_obs(trace: bool, path: str, ttft_target: float):
    """Build the ``--trace`` observer (or None): streaming JSONL sink +
    per-tenant SLO accumulators with a rolling-median companion trace."""
    if not trace:
        return None
    from repro.obs import EngineObs, JsonlSink

    return EngineObs([JsonlSink(path)], ttft_target=ttft_target,
                     smooth_window=9)


def _finish_trace(obs, path: str, recovery: dict | None = None) -> None:
    if obs is None:
        return
    n = obs.sinks[0].emitted
    obs.close()
    print(f"[trace] {n} per-round records streamed to {path}")
    print(obs.render_table(recovery=recovery))


def main_paged(K: int = 16, trace: bool = False) -> None:
    """Mixed-length multi-tenant serving over the block-paged pool: 64
    blocks × 8 tokens serve up to 12 slots (vs 4 dense rings at the same
    HBM), short requests pay short-sequence cost, and the block gauges
    drain back to full."""
    import jax

    from repro.serving.engine_state import (
        make_paged_pool_model,
        paged_pool_admit_fn,
        paged_pool_token_fn,
    )

    NB, BS, vocab = 64, 8, 50
    trace_path = "trace_multitenant.jsonl"
    obs = _make_obs(trace, trace_path, ttft_target=30.0)
    eng = ContinuousBatchingEngine(
        lambda a: None, lambda r: None, n_slots=12, tenants=WEIGHTS,
        kv_pool=(NB, BS, 16), obs=obs)
    eng.megastep_model = make_paged_pool_model(
        jax.random.PRNGKey(0), vocab=vocab, d=16, num_blocks=NB,
        block_size=BS)
    rng = np.random.default_rng(0)
    reqs, rid = [], 0
    for _ in range(30):
        for t in WEIGHTS:
            reqs.append(Request(
                rid=rid, prompt=list(rng.integers(1, vocab, 4)),
                max_new_tokens=int(rng.integers(4, 28)), tenant_id=t))
            rid += 1
    eng.submit_batch(reqs)
    peak_live = 0
    while eng.stats.finished < len(reqs):
        eng.megastep(K, token_fn=paged_pool_token_fn,
                     admit_fn=paged_pool_admit_fn)
        peak_live = max(peak_live, eng.telemetry()["kv_blocks_live"])
    tel = eng.telemetry()
    toks = sum(len(r.out_tokens) for r in reqs)
    print(f"[paged] served {eng.stats.finished} requests / {toks} tokens in "
          f"{eng.stats.host_syncs} host syncs; peak {peak_live}/{NB} blocks "
          f"reserved, now free={tel['kv_blocks_free']} "
          f"live={tel['kv_blocks_live']}")
    # chunked-prefill gauges ride along on every paged engine (all zero in
    # worst-case up-front mode; see examples/serve_longprompt.py for them
    # moving): pool_utilization = blocks actually holding tokens / pool,
    # kv_block_stalls / parked_slots = waiting-array block parks,
    # prefill_chunks = chunk writes
    print(f"[paged] gauges: pool_utilization={tel['pool_utilization']:.0%} "
          f"kv_block_stalls={tel['kv_block_stalls']} "
          f"parked_slots={tel['parked_slots']} "
          f"prefill_chunks={tel['prefill_chunks']}")
    assert tel["kv_blocks_free"] == NB and tel["kv_blocks_live"] == 0
    assert tel["parked_slots"] == 0 and tel["pool_utilization"] == 0.0
    assert all(len(r.out_tokens) == r.max_new_tokens for r in reqs)
    _finish_trace(obs, trace_path)
    print("[example] block-paged KV pool admission + decode OK")


def main_shared_prefix(K: int = 16, trace: bool = False) -> None:
    """Prefix-cache demo (``--shared-prefix``): retry/regenerate traffic
    over one 96-token shared system prompt.  The first wave prefills and
    registers the prefix chain; every later admission attaches the
    covered blocks by incref (zero prefill flops, zero new HBM) and
    prefills only its divergent tail — verbatim repeats skip prefill
    entirely.  Exit asserts: sharing engaged (hits/COW observed), every
    stream completed, and the refcounted conservation identity
    ``free + live(refcnt>0) = NB`` drained back to a full pool."""
    import jax

    from repro.serving.engine_state import (
        make_chunked_prefill_token_fn,
        make_paged_pool_model,
    )

    NB, BS, MB, vocab = 128, 8, 32, 50
    CHUNK, BUDGET = 24, 48
    trace_path = "trace_multitenant.jsonl"
    obs = _make_obs(trace, trace_path, ttft_target=30.0)
    eng = ContinuousBatchingEngine(
        lambda a: None, lambda r: None, n_slots=8, tenants=WEIGHTS,
        kv_pool=(NB, BS, MB), prompt_cap=256,
        chunked_prefill=(CHUNK, BUDGET), prefix_cache=1024, obs=obs)
    eng.megastep_model = make_paged_pool_model(
        jax.random.PRNGKey(0), vocab=vocab, d=16, num_blocks=NB,
        block_size=BS)
    rng = np.random.default_rng(7)
    sysp = list(rng.integers(1, vocab, 96))  # the shared system prompt
    names = list(WEIGHTS)
    reqs = []
    for i in range(30):
        if i >= 8 and i % 2 == 1:
            prompt = list(reqs[i - 2].prompt)  # verbatim regenerate
        else:
            prompt = sysp + list(rng.integers(1, vocab, 5))
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new_tokens=3 + int(rng.integers(0, 5)),
                            tenant_id=names[i % len(names)]))
    eng.submit_batch(reqs)
    shared_peak = 0
    tok_fn = make_chunked_prefill_token_fn(CHUNK)
    while eng.stats.finished < len(reqs):
        eng.megastep(K, token_fn=tok_fn)
        shared_peak = max(shared_peak, eng.telemetry()["blocks_shared"])
    tel = eng.telemetry()
    toks = sum(len(r.out_tokens) for r in reqs)
    print(f"[prefix] served {len(reqs)} requests / {toks} tokens over a "
          f"{len(sysp)}-token shared prefix in {eng.stats.host_syncs} "
          f"host syncs")
    print(f"[prefix] gauges: prefix_hits={tel['prefix_hits']} "
          f"cow_copies={tel['cow_copies']} peak blocks_shared={shared_peak} "
          f"prefill_chunks={tel['prefill_chunks']}")
    assert eng.stats.prefix_hits + eng.stats.cow_copies > 0, \
        "prefix sharing never engaged"
    assert shared_peak > 0
    assert all(len(r.out_tokens) == r.max_new_tokens for r in reqs)
    # refcounted conservation drained: every shared block decref'd to 0
    assert tel["kv_blocks_free"] == NB and tel["blocks_shared"] == 0
    _finish_trace(obs, trace_path)
    print("[example] refcounted prefix cache + copy-on-write sharing OK")


def main_chaos(seed: int = 0, K: int = 8, trace: bool = False) -> None:
    """Self-healing demo (``--chaos [seed]``): a chunked block-paged
    engine with the in-scan sentinels armed is driven through a seeded
    `repro.resilience.FaultPlan` — dropped wake pokes, counter
    corruption, wedged slots, plus one mid-run crash — by the
    `ResilientEngine` recovery ladder: watchdog quarantine + jittered
    requeue, block-table audit-and-rebuild, snapshot/restore with
    deterministic replay.  Every request still drains and the exit
    audit proves conservation at all three semaphore granularities."""
    import tempfile

    from repro.checkpoint.manager import CheckpointManager
    from repro.resilience import CAPACITY_KINDS, FaultPlan, ResilientEngine
    from repro.serving.engine_state import rid_token_fn

    clk = [0.0]
    trace_path = "trace_multitenant.jsonl"
    obs = _make_obs(trace, trace_path, ttft_target=30.0)
    eng = ContinuousBatchingEngine(
        lambda a: np.array([r.rid * 1000 + len(r.out_tokens)
                            for r in a], np.int64),
        lambda r: None, n_slots=4, tenants={"gold": 2.0, "bronze": 1.0},
        clock=lambda: clk[0], kv_pool=(16, 4), chunked_prefill=(5, 9, 16),
        prompt_cap=32, watchdog=4, obs=obs)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=[1 + i % 7] * int(rng.integers(1, 19)),
                    max_new_tokens=1 + int(rng.integers(0, 10)),
                    tenant_id=("gold", "bronze")[int(rng.integers(0, 2))])
            for i in range(12)]
    plan = FaultPlan.random(seed, rounds=24, n_faults=4,
                            kinds=CAPACITY_KINDS).with_crash(11)
    with tempfile.TemporaryDirectory() as ckdir:
        rz = ResilientEngine(eng, plan=plan, react_every=2, retry_budget=2,
                             seed=seed, ckpt=CheckpointManager(ckdir),
                             snapshot_every=8)
        eng.submit_batch(reqs)
        spent = 0
        while spent < 240 and not (
                all(r.done_event.is_set() for r in reqs)
                and not rz._retryq and not eng.active):
            base = eng._round_no
            rz.megastep(K, token_fn=rid_token_fn,
                        nows=np.asarray([(base + k) * 0.25
                                         for k in range(K)], np.float32))
            spent += K
        print(f"[chaos] plan seed={seed}: "
              + ", ".join(f"r{e.round}:{e.kind}" for e in plan.events))
        for e in rz.events:
            extra = {k: v for k, v in e.items()
                     if k not in ("round", "action")}
            print(f"[chaos]   round {e['round']:>3} {e['action']:<12} "
                  f"{extra if extra else ''}")
        rec = rz.telemetry()["recovery"]
        print(f"[chaos] recovery counters: {rec}")
        audit = rz.audit()
        assert all(r.done_event.is_set() for r in reqs), \
            "chaos run failed to drain"
        assert audit["ok"], audit["violations"]
        _finish_trace(obs, trace_path, rec)
        print("[example] fault injection + recovery ladder OK "
              f"(drained {len(reqs)} requests under {len(plan.events)} "
              "injected faults, exit audit clean)")


def main_cluster(seed: int = 0, trace: bool = False,
                 perfetto: str | None = None) -> None:
    """Fault-tolerant multi-engine fabric (``--cluster [seed]``): four
    replica engines behind `repro.serving.router.ReplicaRouter` — each
    replica's in-flight capacity a cluster-level `DistributedTicketLease`
    (grant − ticket = headroom routes the bind), a `LeaseReaper` freeing
    what dead holders leak — driven through a seeded CLUSTER FaultPlan:
    one replica killed mid-megastep, one stalled behind a KV partition
    (declared dead, keeps running as a zombie, fenced when the partition
    heals), one straggler, one leaked lease ticket.  Every accepted
    request completes exactly once or is shed with a recorded reason;
    surviving token streams are bit-identical to a fault-free run; the
    final grant sequence of every lease is clean."""
    from repro.resilience import FaultPlan
    from repro.serving.router import toy_cluster, toy_workload

    trace_path = "trace_multitenant.jsonl"
    plan = FaultPlan.cluster(seed + 3, rounds=10, n_replicas=4)
    work = toy_workload(12, seed=seed + 2)

    baseline = toy_cluster(4, seed=seed)
    baseline.submit_batch(toy_workload(12, seed=seed + 2))
    baseline.run(max_rounds=150)

    obs = _make_obs(trace, trace_path, ttft_target=30.0)
    router = toy_cluster(4, seed=seed, plan=plan, standby=True,
                         snapshot_every=4, obs=obs)
    router.submit_batch(work)
    report = router.run(max_rounds=150)

    print(f"[cluster] plan seed={seed + 3}: "
          + ", ".join(f"r{e.round}:{e.kind}@{e.arg}" for e in plan.events))
    for e in router.events:
        if e["action"] in ("inject", "replica_killed", "replica_dead",
                           "warm_takeover", "fenced", "shed", "reap",
                           "duplicate_suppressed"):
            extra = {k: v for k, v in e.items()
                     if k not in ("round", "action")}
            print(f"[cluster]   round {e['round']:>3} {e['action']:<20} "
                  f"{extra}")
    st = report["stats"]
    print(f"[cluster] completed={st['completed']} shed={report['shed']} "
          f"migrated={st['migrated']} adopted={st['adopted']} "
          f"dupes_suppressed={st['duplicates_suppressed']} "
          f"orphans_reaped={st['orphans_reaped']}")
    done = set(router.completed)
    shed = set(report["shed"])
    assert done | shed == {cr.rid for cr in work} and not (done & shed), \
        "exactly-once violated"
    for rid in done & set(baseline.completed):
        assert router.completed[rid] == baseline.completed[rid], \
            f"rid {rid} stream diverged from fault-free run"
    assert report["lease_audit"]["ok"], report["lease_audit"]["violations"]
    assert all(a["ok"] for a in report["engine_audits"].values())
    recovery = None
    if obs is not None:
        recovery = {}
        for rep in router.replicas:
            for k, v in rep.eng.telemetry()["recovery"].items():
                recovery[k] = recovery.get(k, 0) + v
    _finish_trace(obs, trace_path, recovery)
    # PR 10: stitched cluster spans + fleet aggregation + Perfetto export
    from repro.obs import aggregate, render_cluster_table, write_perfetto

    spans = router.cluster_spans()
    migrated = sum(1 for s in spans.values() if s["migrations"] > 0)
    print(f"[trace] {len(spans)} stitched spans "
          f"({migrated} with a migration segment, "
          f"{sum(s['duplicates_suppressed'] for s in spans.values())} "
          f"duplicate terminals suppressed)")
    # toy_cluster may share ONE recorder across replicas — dedupe so the
    # fleet reduction doesn't count the same accumulator four times
    seen: set[int] = set()
    per_rep = []
    for rep in router.replicas:
        o = rep.eng._obs
        if o is not None and id(o) not in seen:
            seen.add(id(o))
            per_rep.append(o)
    if per_rep:
        print(render_cluster_table(
            aggregate(per_rep, router=router.fabric_telemetry())))
    if perfetto:
        write_perfetto(perfetto, spans)
        print(f"[trace] Chrome-trace JSON written to {perfetto} "
              f"(load in chrome://tracing or ui.perfetto.dev)")
    print("[example] replica router + reaper + exactly-once migration OK "
          f"({st['replicas_dead']} replicas died, "
          f"{st['successors']} warm successors, streams bit-identical)")


def main(use_kernel: bool = False, use_megastep: bool = False, K: int = 16,
         trace: bool = False):
    trace_path = "trace_multitenant.jsonl"
    obs = _make_obs(trace, trace_path, ttft_target=30.0)
    eng = ContinuousBatchingEngine(
        lambda active: np.zeros(len(active)), lambda r: None, n_slots=6,
        tenants=WEIGHTS, use_kernel=use_kernel, obs=obs)
    reqs, rid = [], 0
    for _ in range(120):
        for t in WEIGHTS:
            reqs.append(Request(rid=rid, prompt=[1], max_new_tokens=3,
                                tenant_id=t))
            rid += 1
    # one bronze burst with a deadline that will expire in the queue
    doomed = [Request(rid=rid + i, prompt=[1], max_new_tokens=3,
                      tenant_id="bronze", deadline=time.monotonic() + 0.02)
              for i in range(8)]
    eng.submit_batch(reqs + doomed)
    time.sleep(0.05)  # the doomed deadlines pass while queued

    sat_admitted = None
    steps = 0
    total = len(reqs) + len(doomed)
    while eng.stats.finished + eng.stats.expired < total and steps < 50 * total:
        if sat_admitted is None and not all(d > 0 for d in eng._tenant_live):
            sat_admitted = dict(eng.tenant_admitted)  # saturation window ends
        if use_megastep:
            eng.megastep(K)  # one host sync per K decode rounds
            steps += K
        else:
            eng.step(lambda lg: np.zeros(len(lg), np.int64))
            steps += 1

    tel = eng.telemetry()
    wsum = sum(WEIGHTS.values())
    stot = sum(sat_admitted.values())
    print(f"served {eng.stats.finished} requests in {steps} engine rounds "
          f"({eng.stats.host_syncs} host syncs); "
          f"{eng.stats.expired} deadline-expired (tombstoned)")
    print(f"{'tenant':>8} {'weight':>7} {'sat-share':>10} {'target':>7} "
          f"{'expired':>8}")
    for t, w in WEIGHTS.items():
        share = sat_admitted[t] / stot
        print(f"{t:>8} {w:>7.1f} {share:>10.3f} {w / wsum:>7.3f} "
              f"{tel['tenants'][t]['expired']:>8}")
        assert abs(share - w / wsum) / (w / wsum) < 0.15
    s = eng.stats
    print(f"scheduler examined {s.backlog_scans} rows, skipped "
          f"{s.backlog_skipped} (TWA bucket gating at tenant granularity)")
    assert eng.stats.expired == 8 and eng.stats.finished == len(reqs)
    assert tel["queue_depth"] == 0
    if obs is not None:
        # one record per engine round regardless of host-step vs megastep
        assert obs.rounds == steps, (obs.rounds, steps)
        assert tel["slo"]["tenants"]["bronze"]["expired"] == 8
    _finish_trace(obs, trace_path)
    return eng


if __name__ == "__main__":
    trace = "--trace" in sys.argv[1:]
    if "--chaos" in sys.argv[1:]:
        rest = sys.argv[sys.argv.index("--chaos") + 1:]
        main_chaos(seed=int(rest[0]) if rest and rest[0].isdigit() else 0,
                   trace=trace)
    elif "--cluster" in sys.argv[1:]:
        rest = sys.argv[sys.argv.index("--cluster") + 1:]
        pf = None
        if "--perfetto" in sys.argv[1:]:
            after = sys.argv[sys.argv.index("--perfetto") + 1:]
            pf = (after[0] if after and not after[0].startswith("--")
                  else "trace_cluster.json")
        main_cluster(seed=int(rest[0]) if rest and rest[0].isdigit() else 0,
                     trace=trace, perfetto=pf)
    elif "--paged" in sys.argv[1:]:
        main_paged(trace=trace)
    elif "--shared-prefix" in sys.argv[1:]:
        main_shared_prefix(trace=trace)
    else:
        main(use_kernel="--kernel" in sys.argv[1:],
             use_megastep="--megastep" in sys.argv[1:], trace=trace)
        print("[example] weighted-FCFS admission + tombstoned deadlines OK")
