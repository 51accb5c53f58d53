"""Continuous chunked-prefill subsystem — the PR-5 tentpole tests:

  * property: with ``chunked_prefill=(chunk, budget)`` configured,
    ``megastep(K)`` stays round-for-round bit-identical to K sequential
    ``step()`` calls (both host QoS modes) under mixed prompt/max_new
    lengths that force incremental takes, parks, and resumes — incl. 2³²
    QoS ticket wrap, deadline preemption of mid-prefill and parked slots,
    and the host↔device block-semaphore mirror (ticket/grant/bucket_seq);
  * property: chunked prefill is **chunk-size invariant** — token streams
    through the real pool-attention model are bit-identical for any chunk
    size AND to the one-shot (worst-case up-front) paged engine;
  * property: incremental allocation preserves the PR-4 block-conservation
    invariant (free ∪ tables = {0..NB−1}, no aliasing) under random
    park/resume interleavings and the block counters crossing 2³²;
  * no-deadlock: a pool far smaller than aggregate demand drains
    completely (every sequence finishes), with parks actually exercised,
    and parked slots resume FCFS in Banker priority order;
  * satellite: submit-time ValueError for requests whose lifetime demand
    exceeds pool capacity (instead of stalling forever), and for prompts
    over ``prompt_cap`` (chunked prompts are never truncated);
  * satellite: `telemetry()` gains kv_block_stalls / parked_slots /
    prefill_chunks / pool_utilization;
  * `core.functional.pool_try_alloc` park/wake unit semantics.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, strategies as st

from test_paged_pool import _check_conservation, _fresh_paged_state

from repro.core.functional import (
    make_block_pool,
    pool_free_count,
    pool_release,
    pool_try_alloc,
    woken_mask,
)
from repro.serving.engine_state import (
    chunked_prefill_token_fn,
    engine_round,
    make_chunked_prefill_token_fn,
    make_paged_pool_model,
    paged_pool_admit_fn,
    paged_pool_token_fn,
    rid_token_fn,
)
from repro.serving.events import EV_FINISH
from repro.serving.scheduler import ContinuousBatchingEngine, Request

DT = 0.25  # f32-exact virtual-time grid (see tests/test_megastep.py)


def _rid_step_fn(active):
    return np.array([r.rid * 1000 + len(r.out_tokens) for r in active],
                    np.int64)


_IDENT = lambda lg: lg.astype(np.int64)  # noqa: E731


# ------------------------------------ pool_try_alloc / park_state unit ------


def test_pool_try_alloc_park_and_wake():
    """A parked row's waiting-array bucket moves exactly when enough
    releases landed to cover its deficit — the TWA long-term wait at block
    granularity (wake = re-check hint, FCFS by cursor order)."""
    pool = make_block_pool(8)
    pool, ids, _, _ = pool_try_alloc(
        pool, jnp.asarray([6, 0], jnp.int32), 6,
        park=jnp.asarray([False, False]), deficit=jnp.asarray([0, 0]))
    assert int(pool_free_count(pool)) == 2
    # a row short 3 blocks (needs 5, 2 free) parks with deficit 3
    pool2, _, bkt, seq = pool_try_alloc(
        pool, jnp.asarray([0, 0], jnp.int32), 6,
        park=jnp.asarray([False, True]), deficit=jnp.asarray([0, 3]))
    assert int(pool_free_count(pool2)) == 2
    # 2 releases: not enough — the observed bucket must NOT move
    pool3 = pool_release(pool2, ids[:1, :2], jnp.asarray([True]))
    assert not bool(woken_mask(pool3.sema, seq[1:], bkt[1:])[0])
    # the 3rd release crosses the deficit — the bucket is poked
    pool4 = pool_release(pool3, ids[:1, 2:3], jnp.asarray([True]))
    assert bool(woken_mask(pool4.sema, seq[1:], bkt[1:])[0])


# ------------------------------------ chunked megastep ≡ host loop ----------


def _mk_chunked(clk, *, n_slots=4, kv_pool=(16, 4), chunked=(5, 9, 16),
                use_kernel=True, wrap=False, prompt_cap=32):
    eng = ContinuousBatchingEngine(
        _rid_step_fn, lambda r: None, n_slots,
        tenants={"gold": 2.0, "bronze": 1.0}, use_kernel=use_kernel,
        clock=lambda: clk[0], kv_pool=kv_pool, chunked_prefill=chunked,
        prompt_cap=prompt_cap)
    if wrap:
        base = jnp.uint32((1 << 32) - 7)
        eng.qos = eng.qos._replace(
            ticket=jnp.full((2,), base), grant=jnp.full((2,), base),
            consumed=jnp.full((2,), base))
    return eng


def _workload(seed, n_req, deadline_frac):
    """Prompts up to 18 tokens against a 16×4 pool: first chunks of a
    5-token chunk size demand 1-2 blocks while lifetimes demand up to 7 —
    incremental takes, parks, and resumes all occur."""
    rng = np.random.default_rng(seed)
    names = ["gold", "bronze"]
    reqs = []
    for i in range(n_req):
        dl = DT * int(rng.integers(0, 20)) if rng.random() < deadline_frac \
            else None
        reqs.append(Request(
            rid=i, prompt=[1 + i % 7] * int(rng.integers(1, 19)),
            max_new_tokens=1 + int(rng.integers(0, 10)),
            tenant_id=names[int(rng.integers(0, 2))], deadline=dl))
    return reqs


def _compare_chunked_engines(seed, deadline_frac, wrap, *, use_kernel=True,
                             K=18, n_req=14):
    clk = [0.0]
    eh = _mk_chunked(clk, wrap=wrap, use_kernel=use_kernel)
    em = _mk_chunked(clk, wrap=wrap, use_kernel=use_kernel)
    rh = _workload(seed, n_req, deadline_frac)
    rm = _workload(seed, n_req, deadline_frac)
    eh.submit_batch(rh)
    em.submit_batch(rm)
    times = [k * DT for k in range(K)]
    for t in times:
        clk[0] = t
        eh.step(_IDENT)
    clk[0] = 0.0
    em.megastep(K, token_fn=rid_token_fn, nows=np.asarray(times, np.float32))
    for a, b in zip(rh, rm):
        tag = f"seed={seed} rid={a.rid}"
        assert a.out_tokens == b.out_tokens, (tag, a.out_tokens, b.out_tokens)
        assert a.admit_round == b.admit_round, (tag, a.admit_round,
                                                b.admit_round)
        assert a.expired == b.expired and a.preempted == b.preempted, tag
        assert a.expire_round == b.expire_round, tag
    for a, b in zip(rh, rm):  # prefill/park carry-state of survivors
        if a.slot is not None and not a.expired and a in eh.active.values():
            # past plen the cursor encodings differ (host pins at plen,
            # device reports plen+emitted) but both re-seed identically
            pl = len(a.prompt) or 1
            assert min(a.prefill_pos, pl) == min(b.prefill_pos, pl), \
                (seed, a.rid)
            assert a.parked == b.parked, (seed, a.rid)
            assert a.kv_blocks == b.kv_blocks, (seed, a.rid)
    for f in eh.qos._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(eh.qos, f)), np.asarray(getattr(em.qos, f)),
            err_msg=f"seed={seed}:{f}")
    assert eh._qos_free == em._qos_free
    assert eh._kv_free_blocks == em._kv_free_blocks, seed
    # the host block-semaphore mirror must equal the device pool semaphore
    # (same takes, posts, and waiting-array pokes ⇒ same park/wake rounds)
    dev = em._kv_state.pool.sema
    assert int(eh._kv_sema.ticket) == int(dev.ticket), seed
    assert int(eh._kv_sema.grant) == int(dev.grant), seed
    np.testing.assert_array_equal(np.asarray(eh._kv_sema.bucket_seq),
                                  np.asarray(dev.bucket_seq),
                                  err_msg=str(seed))
    assert eh.stats.admitted == em.stats.admitted
    assert eh.stats.preempted == em.stats.preempted
    assert eh.stats.kv_block_stalls == em.stats.kv_block_stalls, seed
    assert eh.stats.prefill_chunks == em.stats.prefill_chunks, seed


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**32 - 1), st.sampled_from([0.0, 0.4]), st.booleans())
def test_chunked_megastep_equals_host_loop_property(seed, deadline_frac,
                                                    wrap):
    """ISSUE acceptance: chunked megastep(K) ≡ K chunked step() calls,
    round-for-round — token streams, admission/park/resume rounds,
    expiry/preemption, QoS state, free blocks, the block-semaphore
    waiting-array state, and the stall/chunk counters."""
    _compare_chunked_engines(seed, deadline_frac, wrap)


def test_chunked_queue_walk_mode_drives_same_streams():
    """The non-kernel host admission mode (TWA queue walk, lazily poked
    queues) co-schedules the same chunk phase: identical token streams and
    a fully-drained pool — admission ROUND timing may differ from the
    eager kernel path (the walk only re-examines poked queues), so the
    equality is stream-level, not round-level."""
    clk = [0.0]
    ew = _mk_chunked(clk, use_kernel=False)
    ek = _mk_chunked(clk, use_kernel=True)
    rw = _workload(11, 14, 0.0)
    rk = _workload(11, 14, 0.0)
    ew.submit_batch(rw)
    ek.submit_batch(rk)
    for k in range(80):
        clk[0] = k * DT
        ew.step(_IDENT)
        ek.step(_IDENT)
    assert ew.stats.finished == ek.stats.finished == len(rw)
    for a, b in zip(rw, rk):
        assert a.out_tokens == b.out_tokens, a.rid
    assert ew._kv_free_blocks == ek._kv_free_blocks == 16
    assert ew.stats.kv_block_stalls > 0  # parks exercised in walk mode too


# ------------------------------------ chunk-size invariance -----------------


def _attn_run(chunked, *, K=8, n_req=6, n_slots=4, prompt_len=23, vocab=40):
    NB, BS = 32, 4
    eng = ContinuousBatchingEngine(
        lambda a: None, lambda r: None, n_slots, tenants={"a": 1.0},
        clock=lambda: 0.0, kv_pool=(NB, BS, 16), prompt_cap=64,
        chunked_prefill=chunked)
    eng.megastep_model = make_paged_pool_model(
        jax.random.PRNGKey(0), vocab=vocab, d=16, num_blocks=NB,
        block_size=BS)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=list(rng.integers(1, vocab, prompt_len)),
                    max_new_tokens=6, tenant_id="a") for i in range(n_req)]
    eng.submit_batch(reqs)
    tok_fn = chunked_prefill_token_fn if chunked else paged_pool_token_fn
    adm_fn = None if chunked else paged_pool_admit_fn
    launches = 0
    while eng.stats.finished < n_req and launches < 120:
        eng.megastep(K, token_fn=tok_fn, admit_fn=adm_fn)
        launches += 1
    assert eng.stats.finished == n_req
    assert eng.telemetry()["kv_blocks_free"] == NB
    return eng, [r.out_tokens for r in reqs]


@settings(max_examples=3, deadline=None)
@given(st.sampled_from([(2, 5), (4, 16), (7, 7), (16, 64)]))
def test_chunk_size_invariance_property(chunked):
    """ISSUE satellite: chunked prefill (ANY chunk size, aligned or not)
    is bit-identical to one-shot prefill through the REAL pool-attention
    model — the KV a sequence decodes against is independent of how its
    prompt was chunked or which blocks it landed in."""
    _, one_shot = _attn_run(None)
    ec, streams = _attn_run(chunked)
    assert streams == one_shot, chunked
    assert ec.stats.prefill_chunks > 0


def test_chunked_serves_prompts_beyond_oneshot_table():
    """Long-prompt capability: prompts far longer than the one-shot
    in-graph prefill previously handled stream through megastep in chunks
    and decode correctly (same streams for two different chunk sizes)."""
    _, a = _attn_run((6, 12), prompt_len=49, n_req=4)
    _, b = _attn_run((16, 32), prompt_len=49, n_req=4)
    assert a == b
    assert all(len(t) == 6 for t in a)


# ------------------------------------ conservation under park/resume --------


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**32 - 1), st.booleans())
def test_block_conservation_chunked_property(seed, wrap):
    """ISSUE satellite: incremental allocation preserves the PR-4
    conservation invariant — free-queue ∪ live tables = {0..NB−1}, no
    block aliased into two live tables — at every round under random
    park/resume interleavings, incl. the block counters crossing 2³²;
    the workload fully drains (no deadlock at engine-round level)."""
    start = (1 << 32) - 5 if wrap else 0
    state, NB, BS = _fresh_paged_state(12, start=start, seed=seed)
    step = jax.jit(lambda s, now: engine_round(
        s, (), now, token_fn=rid_token_fn, block_size=BS,
        chunk=5, budget=9, commit=NB)[0])

    _check_conservation(state.kv, NB, "init")
    stalls = 0
    for k in range(96):
        state = step(state, k * DT)
        stalls = int(state.stalls)
        _check_conservation(state.kv, NB, f"round {k}")
    assert not bool(np.asarray(state.slots.busy).any())
    assert int(pool_free_count(state.kv.pool)) == NB
    assert stalls >= 0  # counter drained into the state (see no-deadlock test)


# ------------------------------------ no deadlock / FCFS resume -------------


def test_no_deadlock_under_saturation_and_fcfs_resume():
    """A pool an order of magnitude smaller than aggregate demand: every
    sequence still finishes (the headroom invariant keeps one slot always
    runnable), parks are actually exercised, and parked slots RESUME in
    Banker priority order (earliest admission first — strict FCFS, no
    overtaking among equal-tenant sequences)."""
    eng = ContinuousBatchingEngine(
        _rid_step_fn, lambda r: None, 4, tenants={"a": 1.0},
        use_kernel=True, clock=lambda: 0.0, kv_pool=(8, 4),
        chunked_prefill=(4, 8, 8), prompt_cap=32)  # watermark = whole pool
    reqs = [Request(rid=i, prompt=[1] * 14, max_new_tokens=10,
                    tenant_id="a") for i in range(6)]  # 6×6 blocks vs 8
    eng.submit_batch(reqs)
    for _ in range(400):
        eng.step(_IDENT)
        if eng.stats.finished == len(reqs):
            break
    assert eng.stats.finished == len(reqs), "deadlocked under saturation"
    assert eng.stats.kv_block_stalls > 0, "parks never exercised"
    assert eng.telemetry()["kv_blocks_free"] == 8
    assert all(len(r.out_tokens) == 10 for r in reqs)
    # FCFS resume: completion order == admission (ticket) order per tenant
    # (the FINISH events in the order the engine retired the requests)
    fins = [e["uid"] for e in eng._trace.events() if e["kind"] == EV_FINISH]
    assert fins == sorted(r.rid for r in reqs)


def test_headroom_and_watermark_pipeline_admission():
    """Reserved headroom + commitment watermark: while a running long
    sequence still needs most of the pool, a newcomer is NOT admitted
    into its reserve; once the runner's remaining demand drains below the
    watermark the newcomer pipelines in mid-flight — and the headroom
    keeps the runner's tail blocks protected, so BOTH finish (nobody
    deadlocks, nobody is starved)."""
    eng = ContinuousBatchingEngine(
        _rid_step_fn, lambda r: None, 2, tenants={"a": 1.0},
        use_kernel=True, clock=lambda: 0.0, kv_pool=(8, 4),
        chunked_prefill=(4, 4), prompt_cap=32)  # default watermark: 4
    big = Request(rid=0, prompt=[1] * 8, max_new_tokens=20, tenant_id="a")
    eng.submit_batch([big])  # lifetime demand 7 > watermark: bootstraps
    eng.step(_IDENT)
    assert big.slot is not None  # over-watermark yet admitted (alone)
    late = Request(rid=1, prompt=[1] * 4, max_new_tokens=4, tenant_id="a")
    eng.submit_batch([late])
    eng.step(_IDENT)
    assert late.slot is None  # big's outstanding demand holds the gate
    admitted_mid_flight = False
    for _ in range(200):
        eng.step(_IDENT)
        if late.slot is not None and not big.done_event.is_set():
            admitted_mid_flight = True  # pipelined into the drained slack
        if eng.stats.finished == 2:
            break
    assert eng.stats.finished == 2
    assert admitted_mid_flight  # commitment is pipelined, not up-front
    assert big.admit_round < late.admit_round  # FCFS at the gate held
    assert all(len(r.out_tokens) == r.max_new_tokens for r in (big, late))
    assert eng.telemetry()["kv_blocks_free"] == 8


# ------------------------------------ submit-time capacity ValueError -------


def test_submit_rejects_over_capacity_and_over_prompt_cap():
    """ISSUE satellite: a request whose prompt_len + max_new exceeds total
    pool capacity fails at submit with a clear ValueError (it would park
    forever otherwise); chunked prompts longer than prompt_cap are also
    rejected (never truncated)."""
    eng = ContinuousBatchingEngine(
        _rid_step_fn, lambda r: None, 2, tenants={"a": 1.0},
        use_kernel=True, clock=lambda: 0.0, kv_pool=(8, 4),
        chunked_prefill=(4, 8), prompt_cap=64)
    with pytest.raises(ValueError, match="stall forever"):
        eng.submit_batch([Request(rid=0, prompt=[1] * 20, max_new_tokens=20,
                                  tenant_id="a")])  # 40 tokens > 8×4
    with pytest.raises(ValueError, match="prompt_cap"):
        eng.submit_batch([Request(rid=1, prompt=[1] * 65, max_new_tokens=1,
                                  tenant_id="a")])
    # boundary: exactly pool capacity is fine
    eng.submit_batch([Request(rid=2, prompt=[1] * 16, max_new_tokens=16,
                              tenant_id="a")])
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(  # chunked needs the pool
            _rid_step_fn, lambda r: None, 2, tenants={"a": 1.0},
            chunked_prefill=(4, 8))
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(  # degenerate chunk/budget
            _rid_step_fn, lambda r: None, 2, tenants={"a": 1.0},
            kv_pool=(8, 4), chunked_prefill=(0, 8))
    # a token_fn whose static scatter window is narrower than the engine
    # chunk would silently drop chunk tails — rejected at launch
    with pytest.raises(ValueError, match="chunk window"):
        eng.megastep(1, token_fn=make_chunked_prefill_token_fn(2))


# ------------------------------------ telemetry gauges ----------------------


def test_telemetry_chunked_gauges():
    """ISSUE satellite: kv_block_stalls / parked_slots / prefill_chunks /
    pool_utilization ride next to the PR-4 block gauges and track the
    incremental lifecycle."""
    eng = ContinuousBatchingEngine(
        _rid_step_fn, lambda r: None, 2, tenants={"a": 1.0},
        use_kernel=True, clock=lambda: 0.0, kv_pool=(8, 4),
        chunked_prefill=(4, 8), prompt_cap=32)
    tel = eng.telemetry()
    for g in ("kv_block_stalls", "parked_slots", "prefill_chunks",
              "pool_utilization"):
        assert g in tel, g
    assert tel["pool_utilization"] == 0.0
    reqs = [Request(rid=i, prompt=[1] * 12, max_new_tokens=8,
                    tenant_id="a") for i in range(2)]
    eng.submit_batch(reqs)
    eng.step(_IDENT)
    tel = eng.telemetry()
    assert tel["prefill_chunks"] >= 1
    assert 0.0 < tel["pool_utilization"] <= 1.0
    # incremental reservations track written tokens, not worst case:
    # 2 slots × 1 first-chunk block, vs worst-case 2×5 blocks
    assert tel["kv_blocks_live"] <= 4
    while eng.stats.finished < 2:
        eng.step(_IDENT)
    tel = eng.telemetry()
    assert tel["pool_utilization"] == 0.0 and tel["kv_blocks_free"] == 8
    assert tel["parked_slots"] == 0


def test_chunked_pool_utilization_beats_upfront_reservation():
    """A long-prompt mix (16 requests, prompts of 40-79 tokens, decodes of
    64-127) over one 64×8 pool and 8 slots: chunked prefill with
    incremental allocation keeps a higher mean pool utilization than
    worst-case up-front reservation, which holds each sequence's unwritten
    decode tail for its whole life, and both modes serve the same tokens."""
    NB, BS, MB = 64, 8, 26
    S, K, CHUNK, BUDGET = 8, 16, 24, 96
    vocab, n_req = 50, 16
    rng = np.random.default_rng(9)
    plens = rng.integers(40, 80, n_req)
    mxs = rng.integers(64, 128, n_req)
    rng_p = np.random.default_rng(11)
    prompts = [list(rng_p.integers(1, vocab, p)) for p in plens]

    def drain(chunked: bool):
        eng = ContinuousBatchingEngine(
            lambda a: None, lambda r: None, S, tenants={"a": 1.0},
            kv_pool=(NB, BS, MB), prompt_cap=128,
            chunked_prefill=(CHUNK, BUDGET) if chunked else None)
        eng.megastep_model = make_paged_pool_model(
            jax.random.PRNGKey(0), vocab=vocab, d=8, num_blocks=NB,
            block_size=BS)
        tok_fn = (make_chunked_prefill_token_fn(CHUNK) if chunked
                  else paged_pool_token_fn)
        adm_fn = None if chunked else paged_pool_admit_fn
        reqs = [Request(rid=i, prompt=list(p), max_new_tokens=int(mxs[i]),
                        tenant_id="a") for i, p in enumerate(prompts)]
        eng.submit_batch(reqs)
        utils = []
        while eng.stats.finished < n_req:
            eng.megastep(K, token_fn=tok_fn, admit_fn=adm_fn)
            utils.append(eng.telemetry()["pool_utilization"])
        live = [u for u in utils if u > 0]  # the emptying tail left out
        return [r.out_tokens for r in reqs], sum(live) / len(live)

    toks_u, util_u = drain(False)
    toks_c, util_c = drain(True)
    assert toks_c == toks_u
    assert util_c > util_u, (util_c, util_u)
