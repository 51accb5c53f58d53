"""Refcounted prefix sharing — the PR-9 tentpole tests:

  * unit: `serving.prefix` hash/lookup/register round trip — longest
    unbroken chain, tail hits, gen-stamp weak invalidation;
  * property: the refcounted conservation invariant
    ``{free_q[ticket..grant)} ∪ {blocks with refcnt > 0} = {0..NB−1}``
    with ``Σ table references = Σ refcnt`` holds at every round under
    admit / park / preempt / release churn with shared prefixes, incl.
    the block counters crossing 2³²;
  * property: with ``prefix_cache=`` enabled, ``megastep(K)`` stays
    round-for-round bit-identical to K ``step()`` calls — token streams,
    block IDENTITIES (tables, free-queue order, refcounts), the weak
    cache, telemetry samples (prefix_hits / blocks_shared / cow_copies),
    incl. 2³² pool-counter wrap;
  * zero-flop cached prefill: a fully-covered admit attaches by incref
    only — prefill_pos lands AT plen, no prefill chunk is ever
    scheduled for it, and ``prefix_hits`` counts it on both paths;
  * copy-on-write correctness: token streams through the REAL paged
    pool-attention model are bit-identical with sharing on vs off (a
    broken COW would corrupt the shared tail for every sharer);
  * satellite: `submit()` validates lifetime demand against the
    POST-divergence demand when a cached prefix covers part of the
    prompt (admits what the cache makes feasible, still rejects the
    truly infeasible).
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

from repro.core.functional import (
    make_block_pool,
    pool_free_count,
    pool_incref,
    pool_release,
    pool_try_alloc,
)
from repro.resilience.recovery import exit_audit
from repro.serving.engine_state import (
    chunked_prefill_token_fn,
    make_chunked_prefill_token_fn,
    make_paged_pool_model,
    rid_token_fn,
)
from repro.serving.prefix import (
    cache_lookup,
    cache_register,
    make_prefix_cache,
    prompt_hashes,
)
from repro.serving.scheduler import ContinuousBatchingEngine, Request

DT = 0.25  # f32-exact virtual-time grid (see tests/test_megastep.py)

_IDENT = lambda lg: lg.astype(np.int64)  # noqa: E731


def _rid_step_fn(active):
    return np.array([r.rid * 1000 + len(r.out_tokens) for r in active],
                    np.int64)


# --------------------------------------------- prefix cache unit ------------


def test_prompt_hash_lookup_register_roundtrip():
    """Register a completed prefill, look the prefix back up: full blocks
    chain from block 0, the tail entry needs an exact tail length, and a
    release (gen bump) weakly kills every entry for the freed block."""
    BS, W = 4, 4
    pool = make_block_pool(8)
    pool, ids, _, _ = pool_try_alloc(
        pool, jnp.asarray([3], jnp.int32), 3,
        park=jnp.asarray([False]), deficit=jnp.asarray([0]))
    tbl = jnp.asarray([[int(ids[0, 0]), int(ids[0, 1]), int(ids[0, 2]),
                        -1]], jnp.int32)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]       # 2 full blocks + tail 2
    ph = jnp.asarray([prompt_hashes(prompt, BS, W)], jnp.uint32)
    cache = cache_register(make_prefix_cache(16), pool, ph,
                           jnp.asarray([len(prompt)], jnp.int32), tbl,
                           jnp.asarray([True]), BS)
    # identical prompt: full chain + tail hit → covered to plen
    c, bids, tail, cov = cache_lookup(cache, pool, ph,
                                      jnp.asarray([len(prompt)]), BS)
    assert int(c[0]) == 2 and int(cov[0]) == len(prompt)
    assert int(tail[0]) == int(tbl[0, 2])
    assert bids[0, :2].tolist() == [int(tbl[0, 0]), int(tbl[0, 1])]
    # same 2-block prefix, different tail: chain only, no tail hit
    other = prompt[:8] + [7, 7, 7]
    ph2 = jnp.asarray([prompt_hashes(other, BS, W)], jnp.uint32)
    c2, _, tail2, cov2 = cache_lookup(cache, pool, ph2,
                                      jnp.asarray([len(other)]), BS)
    assert int(c2[0]) == 2 and int(tail2[0]) == -1 and int(cov2[0]) == 8
    # free block 1 of the chain → its gen bumps → chain cut at block 1
    pool = pool_release(pool, ids[:, 1:2], jnp.asarray([True]))
    c3, _, tail3, _ = cache_lookup(cache, pool, ph,
                                   jnp.asarray([len(prompt)]), BS)
    assert int(c3[0]) == 1 and int(tail3[0]) == -1


def test_pool_incref_is_semaphore_silent():
    """Attaching a sharer moves NO counter and pokes NO bucket — sharing
    a live block is free at the semaphore level; the release then frees
    only at refcnt 0 (the conditional `post`)."""
    pool = make_block_pool(8)
    pool, ids, _, _ = pool_try_alloc(
        pool, jnp.asarray([2], jnp.int32), 2,
        park=jnp.asarray([False]), deficit=jnp.asarray([0]))
    before = (int(pool.sema.ticket), int(pool.sema.grant),
              np.asarray(pool.sema.bucket_seq).copy())
    pool = pool_incref(pool, ids[0], jnp.ones(2, bool))
    assert int(pool.sema.ticket) == before[0]
    assert int(pool.sema.grant) == before[1]
    np.testing.assert_array_equal(np.asarray(pool.sema.bucket_seq),
                                  before[2])
    assert np.asarray(pool.refcnt)[np.asarray(ids[0])].tolist() == [2, 2]
    # first release: decref only — free count must NOT move
    pool = pool_release(pool, ids, jnp.asarray([True]))
    assert int(pool_free_count(pool)) == 6
    # second release: last sharer leaves → both blocks free
    pool = pool_release(pool, ids, jnp.asarray([True]))
    assert int(pool_free_count(pool)) == 8


# ------------------------------------- refcounted conservation property -----


def _check_refcounted_conservation(pool, tbl, NB, tag=""):
    """The PR-9 generalization of the PR-4 partition check:
    free ∪ {refcnt > 0} tiles {0..NB−1} and table refs == refcnt."""
    t = int(np.uint32(np.asarray(pool.sema.ticket)))
    g = int(np.uint32(np.asarray(pool.sema.grant)))
    free = ((g - t) + (1 << 32)) % (1 << 32)
    assert free <= NB, (tag, free)
    refcnt = np.asarray(pool.refcnt)
    assert (refcnt >= 0).all(), (tag, "negative refcount")
    live = np.flatnonzero(refcnt > 0).tolist()
    assert len(live) == NB - free, (tag, len(live), NB - free)
    fq = np.asarray(pool.free_q)
    free_ids = [int(fq[(t + j) % NB]) for j in range(free)]
    assert sorted(live + free_ids) == list(range(NB)), (tag, "ids lost")
    tb = np.asarray(tbl)
    refs = np.bincount(tb[tb >= 0], minlength=NB)
    np.testing.assert_array_equal(refs, refcnt,
                                  err_msg=f"{tag}: table refs != refcnt")


def _mk_share(clk, *, n_slots=4, kv_pool=(16, 4, 8), chunked=(5, 9, 16),
              prefix=8, use_kernel=True, wrap=False):
    eng = ContinuousBatchingEngine(
        _rid_step_fn, lambda r: None, n_slots,
        tenants={"gold": 2.0, "bronze": 1.0}, use_kernel=use_kernel,
        clock=lambda: clk[0], kv_pool=kv_pool, chunked_prefill=chunked,
        prompt_cap=32, prefix_cache=prefix)
    if wrap:
        # park the replica pool's block-semaphore counters just below 2³²
        # (megastep adopts the replica, so the device wraps identically)
        eng._kv_hpool = make_block_pool(kv_pool[0], table_size=64,
                                        start=(1 << 32) - 5)
        eng._kv_sema = eng._kv_hpool.sema
    return eng


def _share_workload(seed, n_req, deadline_frac):
    """Shared 8-token prefix (2 full blocks) + a random tail: later
    admissions chain onto live blocks; identical-tail collisions produce
    full-prompt hits whose decodes then copy-on-write."""
    rng = np.random.default_rng(seed)
    names = ["gold", "bronze"]
    reqs = []
    for i in range(n_req):
        dl = DT * int(rng.integers(0, 20)) if rng.random() < deadline_frac \
            else None
        tail = [1 + int(x)
                for x in rng.integers(1, 4, int(rng.integers(0, 5)))]
        reqs.append(Request(
            rid=i, prompt=[7] * 8 + tail,
            max_new_tokens=1 + int(rng.integers(0, 6)),
            tenant_id=names[int(rng.integers(0, 2))], deadline=dl))
    return reqs


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**32 - 1), st.sampled_from([0.0, 0.4]),
       st.booleans())
def test_refcounted_conservation_property(seed, deadline_frac, wrap):
    """ISSUE acceptance: the generalized conservation invariant holds at
    EVERY round of a sharing engine under admission, incref attach,
    park/resume, deadline preemption, copy-on-write, and release churn —
    incl. the block counters crossing 2³² — and the drained engine
    passes the refcount-aware exit audit."""
    clk = [0.0]
    eng = _mk_share(clk, wrap=wrap)
    reqs = _share_workload(seed, 12, deadline_frac)
    eng.submit_batch(reqs)
    NB = 16
    for k in range(60):
        clk[0] = k * DT
        eng.step(_IDENT)
        _check_refcounted_conservation(eng._kv_hpool, eng._kv_htbl, NB,
                                       f"seed={seed} round {k}")
        if eng.stats.finished + eng.stats.expired == len(reqs):
            break
    assert eng.stats.finished + eng.stats.expired == len(reqs)
    assert int(pool_free_count(eng._kv_hpool)) == NB
    audit = exit_audit(eng)
    assert audit["ok"], audit["violations"]


# ------------------------------------- sharing megastep ≡ host loop ---------


def _compare_sharing_engines(seed, deadline_frac, wrap, *, K=20, n_req=12):
    clk = [0.0]
    eh = _mk_share(clk, wrap=wrap)
    em = _mk_share(clk, wrap=wrap)
    rh = _share_workload(seed, n_req, deadline_frac)
    rm = _share_workload(seed, n_req, deadline_frac)
    eh.submit_batch(rh)
    em.submit_batch(rm)
    times = [k * DT for k in range(K)]
    for t in times:
        clk[0] = t
        eh.step(_IDENT)
    clk[0] = 0.0
    em.megastep(K, token_fn=rid_token_fn,
                nows=np.asarray(times, np.float32))
    for a, b in zip(rh, rm):
        tag = f"seed={seed} rid={a.rid}"
        assert a.out_tokens == b.out_tokens, (tag, a.out_tokens,
                                              b.out_tokens)
        assert a.admit_round == b.admit_round, tag
        assert a.expired == b.expired and a.preempted == b.preempted, tag
    # block IDENTITIES, not just counters: tables, refcounts, free-queue
    # ORDER, generation stamps, and the weak cache must all agree — any
    # divergence in release batching or slot assignment shows up here
    dev = em._kv_state
    np.testing.assert_array_equal(eh._kv_htbl, np.asarray(dev.tbl),
                                  err_msg=str(seed))
    for f in ("refcnt", "gen", "free_q"):
        np.testing.assert_array_equal(
            np.asarray(getattr(eh._kv_hpool, f)),
            np.asarray(getattr(dev.pool, f)), err_msg=f"seed={seed}:{f}")
    for f in eh._kv_cache._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(eh._kv_cache, f)),
            np.asarray(getattr(dev.cache, f)), err_msg=f"seed={seed}:{f}")
    assert int(eh._kv_sema.ticket) == int(dev.pool.sema.ticket), seed
    assert int(eh._kv_sema.grant) == int(dev.pool.sema.grant), seed
    np.testing.assert_array_equal(np.asarray(eh._kv_sema.bucket_seq),
                                  np.asarray(dev.pool.sema.bucket_seq),
                                  err_msg=str(seed))
    assert eh._kv_free_blocks == em._kv_free_blocks, seed
    assert eh.stats.prefix_hits == em.stats.prefix_hits, seed
    assert eh.stats.cow_copies == em.stats.cow_copies, seed
    assert eh.stats.admitted == em.stats.admitted
    return eh, em


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**32 - 1), st.sampled_from([0.0, 0.4]),
       st.booleans())
def test_sharing_megastep_equals_host_loop_property(seed, deadline_frac,
                                                    wrap):
    """ISSUE acceptance: with the prefix cache enabled, megastep(K) ≡ K
    step() calls bit-identically — including the refcounted pool's full
    identity state and the new telemetry probes — under shared-prefix
    traffic with preemption and 2³² counter wrap."""
    _compare_sharing_engines(seed, deadline_frac, wrap)


def test_sharing_round_samples_bit_identical():
    """The per-round telemetry samples (incl. prefix_hits /
    blocks_shared / cow_copies and the health bitmask) are equal as
    DICTS between a host step and a 1-round megastep, every round."""
    clk = [0.0]
    eh = _mk_share(clk)
    em = _mk_share(clk)
    def wl():
        return [Request(rid=i, prompt=[7] * 10, max_new_tokens=4,
                        tenant_id="gold" if i % 2 else "bronze")
                for i in range(10)]

    eh.submit_batch(wl())
    em.submit_batch(wl())
    shared_seen = 0
    for k in range(24):
        clk[0] = k * DT
        eh.step(_IDENT)
        em.megastep(1, token_fn=rid_token_fn,
                    nows=np.asarray([0.0], np.float32))
        hs, ms = eh._last_samples[-1], em._last_samples[-1]
        assert hs == ms, (k, {key: (hs[key], ms.get(key)) for key in hs
                              if hs[key] != ms.get(key)})
        assert hs["health"] == 0, k
        shared_seen = max(shared_seen, hs["blocks_shared"])
    assert shared_seen > 0, "sharing never engaged"
    assert eh.stats.prefix_hits > 0 and eh.stats.cow_copies > 0


# ------------------------------------- zero-flop cached prefill -------------


def test_fully_covered_admit_skips_prefill_entirely():
    """A request whose WHOLE prompt is cache-resident admits by incref
    only: its KV cursor starts at plen (zero prefill flops — no chunk is
    ever scheduled for it), no new blocks are taken for the covered
    tokens, and prefix_hits counts it."""
    clk = [0.0]
    eng = _mk_share(clk, n_slots=2)
    # long-decoding holder: its blocks stay live (refcnt > 0) so the
    # weak cache entries registered at its prefill completion stay valid
    first = Request(rid=0, prompt=[5] * 10, max_new_tokens=12,
                    tenant_id="gold")
    eng.submit_batch([first])
    k = 0
    while first.prefill_pos < 10:       # registration at completion round
        clk[0] = k * DT
        eng.step(_IDENT)
        k += 1
    assert eng.stats.prefix_hits == 0
    chunks_before = eng.stats.prefill_chunks
    tokens_seen = []
    second = Request(rid=1, prompt=[5] * 10, max_new_tokens=2,
                     tenant_id="gold")
    eng.submit_batch([second])
    for k in range(k, k + 12):
        clk[0] = k * DT
        eng.step(_IDENT)
        tokens_seen.append(eng._last_samples[-1]["prefill_tokens"])
        if second.done_event.is_set():
            break
    assert len(second.out_tokens) == 2
    assert second.prefill_pos >= 10
    assert eng.stats.prefix_hits == 1            # the zero-flop admit
    assert eng.stats.prefill_chunks == chunks_before  # no chunk scheduled
    assert sum(tokens_seen) == 0                 # zero prefill flops
    assert eng.stats.cow_copies >= 1             # tail diverged via COW


# ------------------------------------- COW correctness (real attention) -----


def _attn_share_run(prefix, *, K=8, n_slots=4, vocab=40):
    """Shared-prefix traffic through the REAL pool-attention model —
    identical 16-token system prompt, 7-token user tails.  Lifetimes are
    staggered so later admissions OVERLAP live holders (weak entries die
    with their blocks): rid0 (distinct tail) retires early, rid1 decodes
    long keeping its registered blocks live, and rid2–5 repeat rid1's
    prompt verbatim — full-prompt hits whose decodes then copy-on-write
    the shared tail block."""
    NB, BS = 32, 4
    eng = ContinuousBatchingEngine(
        lambda a: None, lambda r: None, n_slots, tenants={"a": 1.0},
        clock=lambda: 0.0, kv_pool=(NB, BS, 16), prompt_cap=64,
        chunked_prefill=(6, 12), prefix_cache=prefix)
    eng.megastep_model = make_paged_pool_model(
        jax.random.PRNGKey(0), vocab=vocab, d=16, num_blocks=NB,
        block_size=BS)
    rng = np.random.default_rng(9)
    sysp = list(rng.integers(1, vocab, 16))
    tails = [list(rng.integers(1, vocab, 7)) for _ in range(2)]
    mx = [2, 16, 12, 12, 4, 4]
    prompts = [sysp + tails[0]] + [sysp + tails[1]] * 5
    reqs = [Request(rid=i, prompt=list(p), max_new_tokens=mx[i],
                    tenant_id="a") for i, p in enumerate(prompts)]
    n_req = len(reqs)
    eng.submit_batch(reqs)
    launches = 0
    while eng.stats.finished < n_req and launches < 120:
        eng.megastep(K, token_fn=chunked_prefill_token_fn)
        launches += 1
    assert eng.stats.finished == n_req
    assert eng.telemetry()["kv_blocks_free"] == NB
    return eng, [r.out_tokens for r in reqs]


def test_cow_streams_match_no_sharing_through_real_attention():
    """ISSUE acceptance: copy-on-write is CORRECT — token streams through
    the real paged attention are bit-identical with sharing on vs off.
    A COW bug (decode writing into a still-shared block, or a copy
    missing the filled tail) corrupts every sharer's KV and shows here."""
    _, plain = _attn_share_run(0)
    es, shared = _attn_share_run(64)
    assert shared == plain
    # sharing actually happened (prefix attaches and/or COW takes)
    tel = es.telemetry()
    assert tel["prefix_hits"] + tel["cow_copies"] > 0 or \
        es.stats.prefix_hits + es.stats.cow_copies > 0


# ------------------------------------- submit-time post-divergence gate -----


def test_submit_validates_against_post_divergence_demand():
    """ISSUE satellite: lifetime demand beyond pool capacity is accepted
    when a cached prefix covers enough blocks (demand − cached ≤ NB) and
    still rejected when no usable prefix exists."""
    clk = [0.0]
    eng = _mk_share(clk, n_slots=2, kv_pool=(8, 4, 16), chunked=(5, 9, 8),
                    prefix=64)
    wp = [(3 + 7 * i) % 31 + 1 for i in range(24)]  # varied: disperses keys
    bp = wp + [9, 8, 7, 6]
    # no cache yet: 8-block pool, demand cdiv(28 + 8, 4) = 9 > 8 → reject
    big = Request(rid=0, prompt=list(bp), max_new_tokens=8,
                  tenant_id="gold")
    with pytest.raises(ValueError):
        eng.submit(big)
    # warm the cache with a feasible 24-token prompt (6 full blocks) and
    # keep it DECODING — live refcounts keep the weak entries valid
    warm = Request(rid=1, prompt=list(wp), max_new_tokens=6,
                   tenant_id="gold")
    eng.submit_batch([warm])
    k = 0
    while warm.prefill_pos < 24:        # registration at completion round
        clk[0] = k * DT
        eng.step(_IDENT)
        k += 1
        assert k < 30
    # the same over-capacity request now shares 6 cached blocks:
    # post-divergence demand 9 − 6 = 3 ≤ 8 → accepted
    big2 = Request(rid=2, prompt=list(bp), max_new_tokens=8,
                   tenant_id="gold")
    eng.submit(big2)
    # an over-capacity prompt with NO cached prefix still rejects
    alien = Request(rid=3, prompt=[6] * 28, max_new_tokens=8,
                    tenant_id="gold")
    with pytest.raises(ValueError):
        eng.submit(alien)


def test_shared_system_prompt_halves_prefill_tokens():
    """Each of 16 requests opens with the same 224-token system prompt and
    a 9-token tail; after the first wave every other request repeats an
    earlier prompt verbatim while its holder still decodes.  Over one pool,
    sharing prefills under half the prompt tokens that no sharing does,
    the verbatim repeats hit the cache, and without sharing nothing hits."""
    NB, BS, MB = 256, 8, 32
    S, K, CHUNK, BUDGET = 8, 16, 24, 48
    vocab, PRE, TAIL, n_req = 50, 224, 9, 16
    # the shared chain's 28 direct-mapped homes are pairwise distinct at
    # this seed, so no same-sweep collision cuts the chain
    sysp = list(np.random.default_rng(4).integers(1, vocab, PRE))
    rng = np.random.default_rng(3)
    mxs = [int(m) for m in rng.integers(3, 8, n_req)]  # staggered decodes
    prompts = []
    for i in range(n_req):
        if i >= S and i % 2 == 1:
            prompts.append(list(prompts[i - 2]))
        else:
            prompts.append(sysp + list(rng.integers(1, vocab, TAIL)))

    def drain(prefix: int):
        eng = ContinuousBatchingEngine(
            lambda a: None, lambda r: None, S, tenants={"a": 1.0},
            clock=lambda: 0.0, kv_pool=(NB, BS, MB), prompt_cap=256,
            chunked_prefill=(CHUNK, BUDGET), prefix_cache=prefix)
        eng.megastep_model = make_paged_pool_model(
            jax.random.PRNGKey(0), vocab=vocab, d=8, num_blocks=NB,
            block_size=BS)
        tok_fn = make_chunked_prefill_token_fn(CHUNK)
        reqs = [Request(rid=i, prompt=list(p), max_new_tokens=mxs[i],
                        tenant_id="a") for i, p in enumerate(prompts)]
        eng.submit_batch(reqs)
        prefilled = 0
        while eng.stats.finished < n_req:
            eng.megastep(K, token_fn=tok_fn)
            prefilled += sum(s["prefill_tokens"] for s in eng._last_samples)
        return eng, reqs, prefilled

    eng_b, reqs_b, pf_b = drain(0)
    eng_s, reqs_s, pf_s = drain(1024)
    assert ([len(r.out_tokens) for r in reqs_s]
            == [len(r.out_tokens) for r in reqs_b] == mxs)
    assert pf_s < pf_b / 2, (pf_s, pf_b)
    assert eng_s.stats.prefix_hits > 0
    assert eng_b.stats.prefix_hits == 0
