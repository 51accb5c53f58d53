"""Device-resident decode megastep — the engine loop itself as a pure,
scanned JAX program.

`ContinuousBatchingEngine.step()` pays a full host round-trip per decoded
token: Python queue bookkeeping, one dispatch, host-side sampling, per-slot
loops.  The paper's whole point (TWA semaphores make admission latency
near-zero) is squandered if every admission round is bracketed by
milliseconds of host sync.  This module moves the engine in-graph: ONE
jitted `lax.scan` over K decode iterations where all per-slot engine state
lives in a donated on-device :class:`EngineState` pytree, and each scanned
round fuses

  (a) the in-graph multi-tenant QoS admission round (the
      `admission.functional_qos.qos_round` semantics; on TPU the fused
      Pallas pass `kernels.qos_admission.qos_round_fused` — bit-identical,
      see tests/test_qos_kernel.py);
  (b) slot assignment gated by the free-slot TWA semaphore
      (`core.functional` take/post — the reference semantics of the
      `kernels/sema_batch` fused pass): completions/preemptions `post`,
      admissions `take`, and ``grant − ticket`` is the physical free-slot
      count by the paper's counter identity;
  (c) decode + sampling through a caller-supplied jittable ``token_fn``
      (`paged_pool_token_fn` demonstrates paged single-token decode
      attention over the block pool, `paged_pool_admit_fn` its in-graph
      prompt prefill);
  (d) completion AND deadline detection: sequences that hit ``max_new``
      or whose deadline passes mid-decode are tombstoned in-graph and
      their slots posted back into the SAME scanned round machinery —
      a preempted slot's unit re-enters the pool feeding this round's
      replenish, so the next live ticket is re-granted without any host
      involvement (the ROADMAP's deadline-aware decode preemption).

The host syncs once per K tokens — launch plus one drain of the
(K, S) token/event buffers — instead of once per token.

Round order (must mirror `ContinuousBatchingEngine.step()` exactly —
property-tested in tests/test_megastep.py):

  preempt expired running slots  →  QoS admission round (freed units feed
  the same round's replenish)  →  assign free slots to admitted rows in
  wrap-safe FCFS order  →  decode + sample every busy slot  →  retire
  completed slots (their units bank for the next round, exactly the
  host engine's ``_qos_free`` in kernel mode).

Block-paged KV pool (the TWA **block** semaphore ↔ paper mapping)
-----------------------------------------------------------------

With ``kv=`` configured, decode KV lives in a shared pool of fixed-size
blocks instead of per-slot rings, and the allocator is the paper's
semaphore a second time at block granularity (`core.functional.BlockPool`):

  * **units are blocks**: the semaphore's counter identity
    ``grant − ticket`` IS the free-block count, and the counters double as
    the cursors of the circular free queue holding block *identities* —
    an allocation is a wrap-safe `take` of ``demand`` units (ids leave the
    queue at the ticket cursor), a release is a `post` (ids re-enter at
    the grant cursor and the TWAHash buckets of the enabled ticket range
    are poked, staging block waiters for re-examination);
  * **sequences are waiters**: admission gates on BOTH resources — a free
    slot (the QoS round, unchanged) and the sequence's worst-case block
    demand ``⌈(prompt_len + max_new)/block_size⌉``
    (`admission.functional_qos.block_gate`): the longest FCFS prefix of
    QoS-admitted rows whose cumulative demand fits the pool is granted;
    the rest are *block-stalled* — their slot credit is refunded to their
    tenant and they stay live in the backlog, retrying every round (FCFS
    is strict: an unfit row blocks all later rows, so small sequences can
    never starve a large one);
  * **preemption is a tombstoned take**: a deadline-preempted slot's
    blocks are posted back BEFORE this round's admission (they feed the
    same round's gate, like its slot unit feeds the same round's
    replenish); completion posts blocks back after decode, banking them
    for the next round — exactly the slot-unit timing;
  * the per-slot **block tables** (``EngineState.kv.tbl``) map slot ×
    block-ordinal → pool block id; `kernels/paged_decode` streams
    attention over exactly the live blocks (bytes ∝ live tokens, not
    ∝ S·C as with per-slot rings).

Continuous chunked prefill (incremental allocation + the stall/park policy)
---------------------------------------------------------------------------

With ``chunk > 0`` (engine: ``chunked_prefill=(chunk, budget)``), the
worst-case up-front reservation is replaced by **incremental** block
acquisition (`serving.prefill`):

  * admission gates on *first-chunk* demand only; each engine round
    co-schedules prompt chunks with decode under the per-round prefill
    token ``budget`` (Sarathi-style — long prompts stream through without
    monopolizing rounds, decode is never throttled);
  * a sequence takes blocks exactly when it crosses a block boundary —
    prefill chunks take ``⌈(pos+ct)/BS⌉ − held``, decode takes one block
    when its write cursor hits its capacity;
  * **stall policy**: on pool exhaustion the slot PARKS on the block
    semaphore's waiting array (`core.functional.pool_try_alloc`): it
    records the TWAHash bucket of the future grant value that would make
    it runnable and is re-examined only when a release pokes that bucket
    (`core.functional.park_state`) — block-parked slots cost no per-round
    rescan, and resume FCFS because releases enable tickets in cursor
    order.  A parked slot neither prefills nor decodes; preemption and
    completion release its blocks exactly like a running slot's.

  **Headroom invariant (no deadlock).**  For live slots in Banker
  priority order (admission round, FCFS key — `prefill.banker_order`),
  every take and every admission preserves

      rem_i  ≤  free  +  Σ_{j<i} held_j          for all live i,

  i.e. each slot's worst-case remaining demand is covered by the free
  pool plus what its priority-predecessors will release.  The
  priority-first slot therefore never parks; it finishes, releases, and
  hands the cover down — every parked slot is eventually resumed.
  Admission enforces it via the reserved-headroom check in
  `admission.functional_qos.block_gate` (+ `block_headroom`), takes via
  the margin scan in `serving.prefill.chunk_plan`, and the submit-time
  ``demand ≤ pool`` ValueError closes the induction for newcomers.

In-scan telemetry ring (observability without extra host syncs)
---------------------------------------------------------------

With ``ring_cap > 0`` the state carries a :class:`TelemetryRing`: every
scanned round appends one fixed-shape :class:`TelemetrySample` inside the
scan, and the host drains all K samples in the SAME single sync that
drains the token/event buffers — per-round observability is free of
round-trips by construction.  Each probe maps onto a paper construct:

  * ``slot_free`` / ``credit`` / ``kv_free`` — the semaphore **value** at
    each of the three granularities (free slots, per-tenant credit, KV
    blocks), always read through the paper's counter identity
    ``grant − ticket`` (wrap-safe signed distance), never a separate
    gauge that could drift from the counters;
  * ``kv_wait_hist`` — the **waiting-array occupancy** of the block
    semaphore: how many parked slots observe each TWAHash bucket
    (`core.functional.bucket_histogram`).  This is the paper's long-term
    wait made visible — a flat histogram means the salt disperses
    waiters (bounded re-checks per poke), a spike is hash aliasing;
  * ``poke_dead`` — the per-tenant tombstone slack: how far the QoS
    **poke window** over-covers live tickets (the skip-aware grant's
    conservative wake range; `functional_qos.QoSState.dead`);
  * ``kv_pokes`` — cumulative waiting-array pokes of the block semaphore
    (``Σ bucket_seq``): the wake traffic a release fan-out generates;
  * ``gate_stalls`` / ``parked`` — short-term (admission-time) vs
    long-term (mid-sequence) block waiting, the two wait classes the
    paper distinguishes;
  * ``health`` — the in-scan invariant-sentinel bitmask
    (`serving.sentinels`): counter conservation at all three semaphore
    granularities, the block-pool partition audit, Banker headroom, the
    stuck-slot watchdog, and NaN/Inf detection — 0 when every invariant
    holds.  The recovery ladder (`repro.resilience.recovery`) keys its
    escalation off these bits.

The central property extends the repo's spine invariant: the ring of
``megastep(K)`` is **bit-identical** to the concatenation of the K
per-step snapshots the host `ContinuousBatchingEngine.step()` assembles
from its mirrors (tests/test_obs.py — kernel-QoS, paged, and chunked
modes, incl. 2³² counter wrap).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..admission.functional_qos import (
    QoSState,
    block_gate,
    block_headroom,
    qos_scan_round,
)
from ..core.functional import (
    BlockPool,
    SemaState,
    _sdist,
    bucket_histogram,
    make_block_pool,
    make_sema,
    pool_alloc,
    pool_free_count,
    pool_incref,
    pool_release,
    pool_try_alloc,
    post_batch,
    segment_counts,
    take_batch,
)
from .prefill import (
    banker_order,
    cdiv,
    chunk_plan,
    first_chunk_demand,
    pending_prompt_tokens,
    shared_first_chunk_demand,
    total_block_demand,
)
from .prefix import (
    PrefixCache,
    cache_lookup,
    cache_register,
    make_prefix_cache,
)

# admission-order sort key packs (clamped ticket distance, tenant index)
# into one int32: distances beyond ±2²⁰ cannot occur for admitted rows
# (bounded by outstanding grant ≪ backlog capacity), tenant index < 256.
_D_CLAMP = 1 << 20
_T_BITS = 8

# waiting-array table width of the engine-owned semaphores (free-slot sema
# AND the block pool) — also the width of the telemetry ring's occupancy
# histogram.  The scheduler's host mirrors (`_kv_sema`, the host sample's
# bincount) must use the SAME width for the bit-identity property.
SLOT_TABLE = 64


class Backlog(NamedTuple):
    """Waiting requests, device-resident (static capacity B ≥ S)."""

    valid: jax.Array         # (B,) bool — ticketed, not yet admitted/expired
    tenant: jax.Array        # (B,) i32
    ticket: jax.Array        # (B,) u32
    deadline: jax.Array      # (B,) f32 — relative to the megastep epoch
    rid: jax.Array           # (B,) i32
    max_new: jax.Array       # (B,) i32
    prompt: jax.Array        # (B, P) i32 — padded prompt tokens
    prompt_len: jax.Array    # (B,) i32
    admit_round: jax.Array   # (B,) i32 — global round of admission (-1)
    expire_round: jax.Array  # (B,) i32 — global round of expiry (-1)
    slot: jax.Array          # (B,) i32 — slot assigned at admission (-1)
    # -- prompt-prefix sharing (serving.prefix; None when disabled) --
    ph: Optional[jax.Array] = None  # (B, 2, W+1) u32 — prompt hash table
    #                                 (prefix.prompt_hashes, computed on the
    #                                 host at submit — never re-hashed here)


class Slots(NamedTuple):
    """Per-slot decode state (S rows of the batched KV cache)."""

    busy: jax.Array      # (S,) bool
    row: jax.Array       # (S,) i32 — backlog row served (B+s ⇒ active at launch)
    rid: jax.Array       # (S,) i32
    tenant: jax.Array    # (S,) i32
    deadline: jax.Array  # (S,) f32 — decode deadline (preemption), epoch-relative
    max_new: jax.Array   # (S,) i32
    emitted: jax.Array   # (S,) i32 — tokens emitted so far
    token: jax.Array     # (S,) i32 — last token (next decode input)
    pos: jax.Array       # (S,) i32 — KV write cursor / absolute position
    # -- continuous chunked prefill (serving.prefill; inert when chunk=0) --
    plen: jax.Array      # (S,) i32 — prompt length (pos < plen ⇒ prefilling)
    prompt: jax.Array    # (S, P) i32 — the slot's prompt (chunk reads)
    prio_r: jax.Array    # (S,) i32 — admission round (Banker order, primary)
    prio_k: jax.Array    # (S,) i32 — packed FCFS admission key (secondary)
    parked: jax.Array    # (S,) bool — block-stalled on the waiting array
    park_bucket: jax.Array  # (S,) i32 — observed TWAHash bucket (park_state)
    park_seq: jax.Array     # (S,) u32 — bucket sequence at park time
    chunk: jax.Array     # (S,) i32 — prefill tokens scheduled THIS round
    last_adv: jax.Array  # (S,) i32 — last round this slot made progress
    #                      (token emitted / chunk landed / just assigned) —
    #                      the stuck-slot watchdog's clock (sentinels.py)
    # -- prompt-prefix sharing (serving.prefix; None when disabled) --
    ph: Optional[jax.Array] = None       # (S, 2, W+1) u32 — prompt hashes
    #                                      (copied from the backlog row at
    #                                      assignment; cache_register reads)
    cow_src: Optional[jax.Array] = None  # (S,) i32 — copy-on-write source
    #                                      block id staged THIS round (-1 =
    #                                      none): token_fn copies the shared
    #                                      block's contents into the fresh
    #                                      private block before decode writes


class KVPool(NamedTuple):
    """Block-paged KV state: the TWA block semaphore over the circular
    free queue (`core.functional.BlockPool`) plus the per-slot block
    tables the paged-decode kernel streams through.

    Refcounted sharing (PR 9) — the semaphore with a conditional `post`
    ----------------------------------------------------------------------
    With ``cache`` attached, a block may be referenced by SEVERAL slot
    tables at once (a shared prompt prefix).  The paper's semaphore keeps
    owning the block *lifecycle* — the free-queue cursors still satisfy
    ``grant − ticket = free`` and every free block id still lives in
    ``free_q[ticket..grant)`` — but `post` becomes **conditional on the
    refcount**: attaching a sharer (`core.functional.pool_incref`) moves
    no counter and pokes no bucket (sharing a live block is free at the
    semaphore level), and a release (`pool_release`) decrefs first, only
    re-enqueueing the id and poking the waiting array when the LAST
    sharer leaves.  The conservation invariant generalizes to

        {free_q[ticket..grant)} ∪ {blocks with refcnt > 0} = {0..NB−1}
        Σ table references = Σ refcnt

    (the PR-4 one-owner partition is the refcnt ∈ {0,1} special case).
    The ``cache`` itself holds NO references — it is a weak gen-stamped
    index (`serving.prefix.PrefixCache`), so it never delays a free and
    never resurrects a reused block."""

    pool: BlockPool      # free queue + block semaphore (grant−ticket = free)
    tbl: jax.Array       # (S, MB) i32 — per-slot block ids, -1 = unallocated
    cache: Optional[PrefixCache] = None  # weak prefix index (None = no
    #                                      sharing; presence enables the
    #                                      sharing paths — a STATIC pytree
    #                                      property, so both modes stay
    #                                      single-trace)


class TelemetrySample(NamedTuple):
    """One engine round's end-of-round probe set (module docstring maps
    each field to its paper construct).  Fixed-shape so a (R, …) ring of
    them rides the scanned carry; every field is the value AFTER the
    round's completion phase — exactly what the host `step()` path can
    mirror from its own bookkeeping, making megastep(K)'s ring
    bit-identical to K host snapshots."""

    round_no: jax.Array         # i32 — global engine round index
    now: jax.Array              # f32 — the round's clock (epoch-relative)
    admits: jax.Array           # i32 — backlog rows granted a slot
    expires: jax.Array          # i32 — backlog rows tombstoned (deadline)
    preempts: jax.Array         # i32 — running slots deadline-preempted
    tokens: jax.Array           # i32 — slots that emitted a token
    prefill_tokens: jax.Array   # i32 — prompt tokens written this round
    prefill_chunks: jax.Array   # i32 — slots that wrote a prompt chunk
    prefill_pending: jax.Array  # i32 — prompt tokens still unprefilled
    gate_stalls: jax.Array      # i32 — rows block-stalled at the gate
    parked: jax.Array           # i32 — slots parked on the waiting array
    backlog: jax.Array          # i32 — live backlog rows after the round
    active: jax.Array           # i32 — busy slots after the round
    slot_free: jax.Array        # i32 — free-slot sema grant − ticket
    kv_free: jax.Array          # i32 — block sema grant − ticket (0 dense)
    kv_pokes: jax.Array         # u32 — Σ block-sema bucket_seq (mod 2³²)
    prefix_hits: jax.Array      # i32 — fully-covered admits this round
    #                             (prefix cache served the WHOLE prompt:
    #                             zero prefill flops, zero new HBM)
    blocks_shared: jax.Array    # i32 — blocks with refcnt ≥ 2 (end of round)
    cow_copies: jax.Array       # i32 — copy-on-write takes this round
    health: jax.Array           # u32 — invariant-sentinel bitmask
    #                             (serving/sentinels.py; 0 = healthy.  Low
    #                             16 bits are host-mirrorable checks —
    #                             slot/credit/KV counter conservation,
    #                             Banker headroom, stuck-slot watchdog;
    #                             high bits are device-only ground truth:
    #                             block-pool partition audit, NaN/Inf in
    #                             the model.  See HEALTH_BITS.)
    credit: jax.Array           # (T,) i32 — per-tenant grant − consumed
    poke_dead: jax.Array        # (T,) u32 — per-tenant poke-window slack
    kv_wait_hist: jax.Array     # (H,) i32 — waiting-array occupancy
    # ---- per-round trace-event table (PR 10) --------------------------
    # Fixed-shape (E = 8·n_slots) compacted event list: the round's
    # ADMIT / PREFILL_CHUNK / PARK / RESUME / PREFIX_ATTACH / COW /
    # PREEMPT / FINISH records in canonical phase-major, lane-ascending
    # order (`serving.events.SCAN_SEGMENTS`); entries past ``ev_n`` are
    # EV_NONE padding.  The virtual clock of every event is the sample's
    # ``now``.  Host `step()` mirrors the list bit-exactly.
    ev_n: jax.Array             # i32 — number of valid events this round
    ev_kind: jax.Array          # (E,) i32 — serving.events.EV_* kind
    ev_uid: jax.Array           # (E,) i32 — request id (−1 padding)
    ev_slot: jax.Array          # (E,) i32 — engine slot (−1 padding)
    ev_arg: jax.Array           # (E,) i32 — per-kind payload (0 padding)


class TelemetryRing(NamedTuple):
    """Fixed-capacity ring of :class:`TelemetrySample` carried through the
    scan (capacity R = pow2 ≥ K, so one megastep never wraps and the pow2
    mask arithmetic stays exact if a longer-lived ring ever does)."""

    cursor: jax.Array      # i32 — next write index (monotonic)
    buf: TelemetrySample   # every leaf has leading dim R


def make_telemetry_ring(capacity: int, n_tenants: int,
                        hist: int = SLOT_TABLE,
                        ev_cap: int = 0) -> TelemetryRing:
    assert capacity > 0 and (capacity & (capacity - 1)) == 0, \
        "ring capacity must be a power of two (wrap-safe cursor mask)"
    R, T = capacity, n_tenants
    z = jnp.zeros((R,), jnp.int32)
    return TelemetryRing(
        cursor=jnp.zeros((), jnp.int32),
        buf=TelemetrySample(
            round_no=z, now=jnp.zeros((R,), jnp.float32), admits=z,
            expires=z, preempts=z, tokens=z, prefill_tokens=z,
            prefill_chunks=z, prefill_pending=z, gate_stalls=z, parked=z,
            backlog=z, active=z, slot_free=z, kv_free=z,
            kv_pokes=jnp.zeros((R,), jnp.uint32),
            prefix_hits=z, blocks_shared=z, cow_copies=z,
            health=jnp.zeros((R,), jnp.uint32),
            credit=jnp.zeros((R, T), jnp.int32),
            poke_dead=jnp.zeros((R, T), jnp.uint32),
            kv_wait_hist=jnp.zeros((R, hist), jnp.int32),
            ev_n=z,
            ev_kind=jnp.zeros((R, ev_cap), jnp.int32),
            ev_uid=jnp.full((R, ev_cap), -1, jnp.int32),
            ev_slot=jnp.full((R, ev_cap), -1, jnp.int32),
            ev_arg=jnp.zeros((R, ev_cap), jnp.int32)))


def ring_append(ring: TelemetryRing, sample: TelemetrySample) -> TelemetryRing:
    R = ring.buf.round_no.shape[0]
    idx = ring.cursor & (R - 1)
    return TelemetryRing(
        cursor=ring.cursor + 1,
        buf=jax.tree_util.tree_map(
            lambda b, s: b.at[idx].set(s), ring.buf, sample))


def ring_samples(ring, t0: float = 0.0) -> list:
    """Host-side drain: the ring (already device_get, as part of the ONE
    megastep sync) as a list of per-round dicts in round order, oldest
    first — the exact record shape `ContinuousBatchingEngine.step()`
    assembles per host round, so the two paths compare with ``==``.
    ``t0`` re-anchors the epoch-relative round clocks to the engine's
    absolute clock (``clock = t0 + now``)."""
    import numpy as np

    buf, n = ring.buf, int(ring.cursor)
    R = np.asarray(buf.round_no).shape[0]
    out = []
    for i in range(max(n - R, 0), n):
        k = i & (R - 1)
        out.append({
            "round": int(buf.round_no[k]),
            "clock": float(t0) + float(buf.now[k]),
            "admits": int(buf.admits[k]),
            "expires": int(buf.expires[k]),
            "preempts": int(buf.preempts[k]),
            "tokens": int(buf.tokens[k]),
            "prefill_tokens": int(buf.prefill_tokens[k]),
            "prefill_chunks": int(buf.prefill_chunks[k]),
            "prefill_pending": int(buf.prefill_pending[k]),
            "gate_stalls": int(buf.gate_stalls[k]),
            "parked": int(buf.parked[k]),
            "backlog": int(buf.backlog[k]),
            "active": int(buf.active[k]),
            "slot_free": int(buf.slot_free[k]),
            "kv_free": int(buf.kv_free[k]),
            "kv_pokes": int(buf.kv_pokes[k]),
            "prefix_hits": int(buf.prefix_hits[k]),
            "blocks_shared": int(buf.blocks_shared[k]),
            "cow_copies": int(buf.cow_copies[k]),
            "health": int(buf.health[k]),
            "credit": [int(c) for c in np.asarray(buf.credit[k])],
            "poke_dead": [int(d) for d in np.asarray(buf.poke_dead[k])],
            "kv_wait_hist": [int(h) for h in
                             np.asarray(buf.kv_wait_hist[k])],
            "events": [[int(ek), int(eu), int(es), int(ea)]
                       for ek, eu, es, ea in zip(
                           np.asarray(buf.ev_kind[k])[:int(buf.ev_n[k])],
                           np.asarray(buf.ev_uid[k])[:int(buf.ev_n[k])],
                           np.asarray(buf.ev_slot[k])[:int(buf.ev_n[k])],
                           np.asarray(buf.ev_arg[k])[:int(buf.ev_n[k])])],
        })
    return out


class EngineState(NamedTuple):
    """The donated on-device engine pytree carried through the scan."""

    qos: QoSState        # per-tenant semaphores + shared waiting array
    slot_sema: SemaState  # free-slot TWA semaphore (grant − ticket = free)
    free: jax.Array      # i32 scalar — undistributed global slot pool
    round_no: jax.Array  # i32 scalar — global engine round counter
    backlog: Backlog
    slots: Slots
    kv: Optional[KVPool] = None  # block-paged KV pool (None = no pool)
    stalls: Optional[jax.Array] = None  # i32 — cumulative parked slot-rounds
    chunks: Optional[jax.Array] = None  # i32 — cumulative prefill chunks
    ring: Optional[TelemetryRing] = None  # in-scan telemetry (None = off)


class RoundOut(NamedTuple):
    """Per-iteration scan outputs drained by the host once per megastep."""

    tokens: jax.Array  # (S,) i32 — token emitted by each slot this round
    emit: jax.Array    # (S,) bool — slot decoded this round
    fin: jax.Array     # (S,) bool — slot completed (hit max_new) this round
    pre: jax.Array     # (S,) bool — slot deadline-preempted this round
    row: jax.Array     # (S,) i32 — backlog row at emit time
    prerow: jax.Array  # (S,) i32 — backlog row at preemption time
    n_live: jax.Array  # i32 — backlog rows examined by the admission round
    n_active: jax.Array  # i32 — busy slots at decode time


# TokenFn: (model, EngineState) -> (next_tokens (S,) i32, model')
TokenFn = Callable
# AdmitFn: (model, EngineState, rows (S,) i32, mask (S,) bool,
#           slots (S,) i32) -> model'   — in-graph prefill hook
AdmitFn = Optional[Callable]


def make_engine_state(qos: QoSState, n_slots: int, backlog_cap: int,
                      prompt_cap: int, *, free_units=0,
                      slot_table: int = SLOT_TABLE, kv_blocks: int = 0,
                      kv_slot_blocks: int = 0, ring_cap: int = 0,
                      prefix_entries: int = 0,
                      hash_width: int = 0) -> EngineState:
    """Fresh device state (empty backlog, idle slots).  The scheduler
    refreshes backlog/slot rows from its host queues at each launch; the
    QoS state is the one source of truth shared with the host path.
    ``kv_blocks`` > 0 attaches a block-paged KV pool of that many blocks
    (power of two) with ``kv_slot_blocks``-entry per-slot block tables.
    ``ring_cap`` > 0 (power of two ≥ the scan length) attaches the
    in-scan :class:`TelemetryRing` (module docstring).
    ``prefix_entries`` > 0 (power of two; requires the pool) attaches the
    weak prefix cache and the prompt-hash / copy-on-write slot state that
    enable refcounted block sharing; ``hash_width`` is the per-prompt
    hash-table width W (``prompt_cap // block_size`` — one entry per full
    block boundary plus the full-prompt column)."""
    assert backlog_cap >= n_slots, "backlog capacity must cover the slots"
    assert prefix_entries == 0 or kv_blocks > 0, \
        "prefix sharing needs the block-paged pool"
    S, B, P = n_slots, backlog_cap, prompt_cap
    W = hash_width
    zb = jnp.zeros((B,), jnp.int32)
    kv = None
    if kv_blocks:
        assert kv_slot_blocks > 0, "paged pool needs a per-slot table size"
        kv = KVPool(pool=make_block_pool(kv_blocks, table_size=slot_table),
                    tbl=jnp.full((S, kv_slot_blocks), -1, jnp.int32),
                    cache=(make_prefix_cache(prefix_entries)
                           if prefix_entries else None))
    ring = None
    if ring_cap:
        # event-table capacity: 8 phase segments of S lanes each — every
        # kind can fire on at most S lanes per round, so the compacted
        # table never overflows (serving.events.SCAN_SEGMENTS)
        ring = make_telemetry_ring(ring_cap, qos.ticket.shape[0],
                                   hist=slot_table, ev_cap=8 * n_slots)
    return EngineState(
        kv=kv,
        ring=ring,
        qos=qos,
        slot_sema=make_sema(count=n_slots, table_size=slot_table),
        free=jnp.asarray(free_units, jnp.int32),
        round_no=jnp.zeros((), jnp.int32),
        stalls=jnp.zeros((), jnp.int32),
        chunks=jnp.zeros((), jnp.int32),
        backlog=Backlog(
            valid=jnp.zeros((B,), bool), tenant=zb,
            ticket=jnp.zeros((B,), jnp.uint32),
            deadline=jnp.full((B,), jnp.inf, jnp.float32),
            rid=jnp.full((B,), -1, jnp.int32), max_new=zb,
            prompt=jnp.zeros((B, P), jnp.int32), prompt_len=zb,
            admit_round=jnp.full((B,), -1, jnp.int32),
            expire_round=jnp.full((B,), -1, jnp.int32),
            slot=jnp.full((B,), -1, jnp.int32),
            ph=(jnp.zeros((B, 2, W + 1), jnp.uint32)
                if prefix_entries else None)),
        slots=Slots(
            busy=jnp.zeros((S,), bool),
            row=jnp.full((S,), -1, jnp.int32),
            rid=jnp.full((S,), -1, jnp.int32),
            tenant=jnp.zeros((S,), jnp.int32),
            deadline=jnp.full((S,), jnp.inf, jnp.float32),
            max_new=jnp.zeros((S,), jnp.int32),
            emitted=jnp.zeros((S,), jnp.int32),
            token=jnp.zeros((S,), jnp.int32),
            pos=jnp.zeros((S,), jnp.int32),
            plen=jnp.zeros((S,), jnp.int32),
            prompt=jnp.zeros((S, P), jnp.int32),
            prio_r=jnp.zeros((S,), jnp.int32),
            prio_k=jnp.zeros((S,), jnp.int32),
            parked=jnp.zeros((S,), bool),
            park_bucket=jnp.zeros((S,), jnp.int32),
            park_seq=jnp.zeros((S,), jnp.uint32),
            chunk=jnp.zeros((S,), jnp.int32),
            last_adv=jnp.zeros((S,), jnp.int32),
            ph=(jnp.zeros((S, 2, W + 1), jnp.uint32)
                if prefix_entries else None),
            cow_src=(jnp.full((S,), -1, jnp.int32)
                     if prefix_entries else None)),
    )


def _fcfs_key(backlog: Backlog, grant: jax.Array, mask: jax.Array):
    """Packed global admission-order key (wrap-safe signed ticket distance
    from the post-round grant frontier, tenant-index tiebreak); rows
    outside ``mask`` get the INT32_MAX sentinel.  Shared by slot
    assignment and the block gate — host and device MUST sort by the same
    total order for the multi-resource prefix to be bit-identical
    (`ContinuousBatchingEngine._kv_gate` mirrors this in numpy)."""
    d = _sdist(backlog.ticket, grant[backlog.tenant])
    return jnp.where(
        mask,
        (jnp.clip(d, -_D_CLAMP, _D_CLAMP) << _T_BITS) + backlog.tenant,
        jnp.iinfo(jnp.int32).max)


def _block_demand(backlog: Backlog, block_size: int) -> jax.Array:
    """Worst-case block demand per backlog row: every token the sequence
    can ever hold (truncated prompt + max_new) — acquired in full at
    admission in up-front mode; the commitment watermark's per-row demand
    in chunked mode."""
    return total_block_demand(backlog.prompt_len, backlog.max_new,
                              block_size)


def _slot_rem(sl: Slots, held: jax.Array, block_size: int) -> jax.Array:
    """Worst-case REMAINING block demand per slot (the safety invariant's
    ``rem``): whole-lifetime demand minus the blocks already held; 0 for
    idle slots."""
    total = total_block_demand(sl.plen, sl.max_new, block_size)
    return jnp.where(sl.busy, total - held, 0)


def _share_flags(tbl: jax.Array, refcnt: jax.Array, busy: jax.Array,
                 pos: jax.Array, plen: jax.Array, held: jax.Array,
                 block_size: int):
    """The two per-slot sharing inputs of `serving.prefill.chunk_plan`,
    in ONE canonical formulation (host `_chunk_step` and the scanned
    round both call this — the formulas must never fork):

      ``cow``: the slot is decode-ready and its NEXT write would land in
      its current tail block while that block is still shared
      (``refcnt > 1``) — it must take a private copy first;
      ``held_free``: how many of the slot's held blocks it alone
      references (``refcnt == 1``) — the only ones whose release will
      actually free pool capacity (the Banker cover).

    Returns ``(cow (S,) bool, held_free (S,) i32)``.
    """
    S, MB = tbl.shape
    NB = refcnt.shape[0]
    rows_i = jnp.arange(S, dtype=jnp.int32)
    cur = tbl[rows_i, jnp.clip(held - 1, 0, MB - 1)]
    cow = (busy & (pos >= plen) & (pos < held * block_size) & (cur >= 0)
           & (refcnt[jnp.clip(cur, 0, NB - 1)] > 1))
    priv = (tbl >= 0) & (refcnt[jnp.clip(tbl, 0, NB - 1)] == 1)
    held_free = jnp.sum(priv.astype(jnp.int32), axis=1)
    return cow, held_free


def _chunk_phase(state: EngineState, chunk: int, budget: int,
                 block_size: int):
    """The chunked-prefill slice of one engine round: plan this round's
    chunks/takes/parks (`serving.prefill.chunk_plan` over the Banker
    order), take the granted blocks from the TWA block semaphore
    (`core.functional.pool_try_alloc` — parked slots register on the
    waiting array instead), scatter the fresh ids into the slot tables,
    and stage the per-slot chunk lengths for ``token_fn``.  With the
    prefix cache attached the plan additionally carries copy-on-write
    takes (`_share_flags`): a granted COW block REPLACES the slot's
    shared tail block in the table, the replaced id is decref'd in ONE
    batched `pool_release`, and ``slots.cow_src`` stages the source id
    for token_fn's in-pass block copy.  Returns ``(state', emit, n_cow,
    ev)`` — the decode mask, the round's copy-on-write count, and the
    trace-event masks/args (PARK transitions with their deficits, RESUME
    transitions, chunk token counts, COW takes with the replaced block
    ids) the caller folds into the in-scan event table."""
    sl, kv = state.slots, state.kv
    prev_parked = sl.parked  # pre-plan park state (PARK/RESUME transitions)
    sharing = kv.cache is not None
    S, MB = kv.tbl.shape
    held = jnp.sum((kv.tbl >= 0).astype(jnp.int32), axis=1)
    # TWA wake gate: parked slots re-attempt only when a release poked
    # their observed bucket (spurious wakes from hash aliasing are benign
    # re-checks; a missed state change is impossible — free−guard grows
    # only via releases, and every release pokes the enabled range).
    woken = kv.pool.sema.bucket_seq[sl.park_bucket] != sl.park_seq
    if sharing:
        cow, held_free = _share_flags(kv.tbl, kv.pool.refcnt, sl.busy,
                                      sl.pos, sl.plen, held, block_size)
    else:  # chunk_plan reduces bit-identically to the PR-5 plan
        cow, held_free = jnp.zeros((S,), bool), held
    rem = _slot_rem(sl, held, block_size) + jnp.where(cow, 1, 0)
    order = banker_order(rem, sl.prio_r, sl.prio_k, sl.busy)
    plan = chunk_plan(order, sl.busy, sl.parked, woken, sl.pos, sl.plen,
                      sl.max_new, held, pool_free_count(kv.pool), cow,
                      held_free, chunk=chunk, budget=budget,
                      block_size=block_size)
    newly = plan.parked & (plan.deficit > 0)
    max_take = cdiv(chunk, block_size) + 1  # a chunk can straddle a block
    pool, ids, bkt, seq = pool_try_alloc(kv.pool, plan.take, max_take,
                                         park=newly, deficit=plan.deficit)
    k = jnp.arange(max_take, dtype=jnp.int32)
    rowi = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[:, None],
                            (S, max_take))
    valid = k[None, :] < plan.take[:, None]
    # a COW grant replaces the shared tail (column held−1) instead of
    # extending the table; the replaced id is read BEFORE the scatter
    base = jnp.where(plan.cow, held - 1, held) if sharing else held
    old = kv.tbl[jnp.arange(S, dtype=jnp.int32),
                 jnp.clip(held - 1, 0, MB - 1)]
    tbl = kv.tbl.at[jnp.where(valid, rowi, S),
                    base[:, None] + k[None, :]].set(ids, mode="drop")
    n_cow = jnp.int32(0)
    if sharing:
        # ONE batched decref of every replaced shared block (identity on
        # an empty mask; cond-skipped at runtime — most rounds copy
        # nothing.  The host replica issues the SAME single batched call,
        # keeping free-queue id order identical.)
        pool = jax.lax.cond(
            jnp.any(plan.cow),
            lambda p: pool_release(p, old, plan.cow),
            lambda p: p, pool)
        n_cow = jnp.sum(plan.cow.astype(jnp.int32))
    sl = sl._replace(
        chunk=plan.tokens, parked=plan.parked,
        park_bucket=jnp.where(newly, bkt, sl.park_bucket),
        park_seq=jnp.where(newly, seq, sl.park_seq),
        cow_src=(jnp.where(plan.cow, old, -1) if sharing else sl.cow_src))
    state = state._replace(
        kv=KVPool(pool=pool, tbl=tbl, cache=kv.cache), slots=sl,
        stalls=state.stalls + jnp.sum(plan.parked.astype(jnp.int32)),
        chunks=state.chunks + jnp.sum((plan.tokens > 0).astype(jnp.int32)))
    ev = {
        "park": plan.parked & ~prev_parked,
        "park_arg": plan.deficit,
        "resume": prev_parked & ~plan.parked,
        "chunk_tok": plan.tokens,
        "cow": plan.cow if sharing else jnp.zeros_like(plan.parked),
        "cow_old": old,
    }
    return state, plan.emit, n_cow, ev


def _assign_slots(state: EngineState, admitted: jax.Array,
                  chunked: bool = False, cov=None):
    """Map admitted backlog rows to free slots: rows in wrap-safe per-tenant
    FCFS admission order (signed ticket distance from the post-round grant
    frontier, tenant index tiebreak — the in-graph `_fcfs_sort`) take
    ascending free slot indices, gated through the free-slot TWA semaphore
    (admissions `take`; the QoS invariant guarantees n_admitted ≤ free).

    Every slot records its Banker priority — (admission round, FCFS key) —
    at assignment; ``chunked`` starts the KV cursor at 0 (the prompt is
    prefilled chunk-by-chunk) instead of at ``prompt_len`` (instant
    prefill) and copies the prompt into the slot row so later chunks can
    read it after the backlog row is recycled.  With prefix sharing,
    ``cov`` (B,) i32 is each row's cache-covered token count — the KV
    cursor starts AT the divergence point (the covered tokens are
    already resident in the shared blocks the caller attaches)."""
    sl, bl = state.slots, state.backlog
    S = sl.busy.shape[0]
    B = bl.valid.shape[0]

    key = _fcfs_key(bl, state.qos.grant, admitted)
    order = jnp.argsort(key, stable=True)        # admitted rows first, FCFS
    n_adm = jnp.sum(admitted.astype(jnp.int32))

    j = jnp.arange(S, dtype=jnp.int32)
    rows = order[:S]                              # j-th admitted row (B ≥ S)
    assign = j < n_adm
    free_order = jnp.argsort(sl.busy, stable=True)  # free slots ascending
    tgt = jnp.where(assign, free_order[:S], S)      # S = out-of-range → drop

    slot_sema, _, _, _ = take_batch(state.slot_sema, assign)
    seed_tok = bl.prompt[rows, jnp.maximum(bl.prompt_len[rows] - 1, 0)]
    if cov is not None:  # sharing: resume past the cache-covered prefix
        pos0 = cov[rows]
    else:
        pos0 = jnp.zeros_like(rows) if chunked else bl.prompt_len[rows]
    slots = Slots(
        busy=sl.busy.at[tgt].set(True, mode="drop"),
        row=sl.row.at[tgt].set(rows, mode="drop"),
        rid=sl.rid.at[tgt].set(bl.rid[rows], mode="drop"),
        tenant=sl.tenant.at[tgt].set(bl.tenant[rows], mode="drop"),
        deadline=sl.deadline.at[tgt].set(bl.deadline[rows], mode="drop"),
        max_new=sl.max_new.at[tgt].set(bl.max_new[rows], mode="drop"),
        emitted=sl.emitted.at[tgt].set(0, mode="drop"),
        token=sl.token.at[tgt].set(seed_tok, mode="drop"),
        pos=sl.pos.at[tgt].set(pos0, mode="drop"),
        plen=sl.plen.at[tgt].set(bl.prompt_len[rows], mode="drop"),
        prompt=sl.prompt.at[tgt].set(bl.prompt[rows], mode="drop"),
        prio_r=sl.prio_r.at[tgt].set(state.round_no, mode="drop"),
        prio_k=sl.prio_k.at[tgt].set(key[rows], mode="drop"),
        parked=sl.parked.at[tgt].set(False, mode="drop"),
        park_bucket=sl.park_bucket.at[tgt].set(0, mode="drop"),
        park_seq=sl.park_seq.at[tgt].set(jnp.uint32(0), mode="drop"),
        chunk=sl.chunk.at[tgt].set(0, mode="drop"),
        last_adv=sl.last_adv.at[tgt].set(state.round_no, mode="drop"),
        ph=(sl.ph.at[tgt].set(bl.ph[rows], mode="drop")
            if sl.ph is not None else None),
        cow_src=(sl.cow_src.at[tgt].set(-1, mode="drop")
                 if sl.cow_src is not None else None))
    bslot = bl.slot.at[jnp.where(assign, rows, B)].set(tgt, mode="drop")
    return state._replace(slots=slots, slot_sema=slot_sema,
                          backlog=bl._replace(slot=bslot)), rows, assign, tgt


def engine_round(state: EngineState, model, now, *, token_fn: TokenFn,
                 admit_fn: AdmitFn = None, admit_impl=None,
                 block_size: int = 0, chunk: int = 0, budget: int = 0,
                 commit: int = 0, watchdog: int = 0):
    """One fused engine iteration — the pure-functional `step()`.

    ``admit_impl`` overrides the admission-round implementation (signature
    of `functional_qos.qos_round`); the default is the functional path, and
    the scheduler substitutes `kernels.qos_admission.qos_round_fused` on
    TPU (bit-identical — tests/test_qos_kernel.py).

    With ``state.kv`` set (block-paged KV pool), ``block_size`` must be the
    static pool block size: admission additionally gates on worst-case
    block demand (see the module docstring's block-semaphore mapping).

    ``chunk > 0`` selects **continuous chunked prefill** (requires the
    pool): admission gates on first-chunk demand behind the reserved
    headroom AND the ``commit``-block commitment watermark, prompts
    prefill ``chunk`` tokens per round under the per-round prefill token
    ``budget``, blocks are taken incrementally at block-boundary
    crossings, and block-stalled slots park on the block semaphore's
    waiting array (module docstring; `serving.prefill`).  ``token_fn``
    must then handle the prefill phase — see
    :func:`chunked_prefill_token_fn`.

    ``watchdog > 0`` arms the stuck-slot sentinel: a busy slot that makes
    no progress for ``watchdog`` consecutive rounds sets ``H_STUCK`` in
    the round's health bitmask (`serving.sentinels` — requires the
    telemetry ring to be observable).
    """
    paged = state.kv is not None
    assert not paged or block_size > 0, "paged pool needs block_size"
    chunked = chunk > 0
    assert not chunked or (paged and budget > 0), \
        "chunked prefill needs the block pool and a positive token budget"
    # prefix sharing is a STATIC pytree property (cache present or not) —
    # both modes trace once, and the no-sharing trace is unchanged
    sharing = paged and state.kv.cache is not None
    assert not sharing or chunked, \
        "prefix sharing requires continuous chunked prefill"
    sl, bl = state.slots, state.backlog
    S = sl.busy.shape[0]
    now = jnp.asarray(now, jnp.float32)

    # (1) deadline-aware decode preemption: expired RUNNING sequences are
    # tombstoned and their slots posted back into THIS round's pool.
    pre = sl.busy & (sl.deadline <= now)
    n_pre = jnp.sum(pre.astype(jnp.int32))
    prerow = jnp.where(pre, sl.row, -1)
    # trace: capture uid/progress at ENTRY — the slot may be re-assigned
    # to a new request later this same round (its unit feeds this round's
    # pool), which would overwrite rid/emitted before the phase-6 table
    pre_uid, pre_arg = sl.rid, sl.emitted
    sl = sl._replace(busy=sl.busy & ~pre,
                     row=jnp.where(pre, -1, sl.row),
                     parked=sl.parked & ~pre)
    state = state._replace(slots=sl, slot_sema=post_batch(state.slot_sema, n_pre))
    if paged:
        # preempted slots' blocks post back BEFORE admission — they feed
        # THIS round's block gate, mirroring the slot-unit feedback.  The
        # release is an identity on an empty mask, so it is cond-skipped
        # at runtime (most rounds preempt nothing — real wall-time inside
        # the compiled scan, bit-identical either way).
        state = state._replace(kv=jax.lax.cond(
            jnp.any(pre), lambda kv: KVPool(
                pool=pool_release(kv.pool, kv.tbl, pre),
                tbl=jnp.where(pre[:, None], -1, kv.tbl),
                cache=kv.cache),
            lambda kv: kv, state.kv))

    # (2) the QoS admission round, preemption-freed units feeding replenish.
    # The round only runs when live rows exist — the host path's early
    # return on an empty backlog (an unconditional round would still poke
    # the dead-slack window and drift bucket_seq off the host oracle).
    alive = bl.valid

    def _round(args):
        qos, free = args
        return qos_scan_round(qos, bl.tenant, bl.ticket, alive, bl.deadline,
                              now, free, n_pre, max_units=S,
                              round_impl=admit_impl)

    def _skip(args):
        qos, free = args
        no = jnp.zeros(alive.shape, bool)
        return qos, no, no, free + n_pre

    qos, admitted, expired, leftover = jax.lax.cond(
        jnp.any(alive), _round, _skip, (state.qos, state.free))

    # (2b) multi-resource gate: of the QoS-admitted rows, only the FCFS
    # prefix whose cumulative block demand fits the free pool is granted;
    # block-stalled rows refund their tenant's slot credit and stay live
    # in the backlog (they retry every round).  Cond-skipped when the QoS
    # round admitted nothing (gate/refund are identities on an empty mask
    # — the host path's ``admitted.any()`` early-out).  Chunked prefill
    # gates on FIRST-CHUNK demand only, behind the reserved headroom that
    # keeps the no-deadlock invariant (module docstring).
    doomed = None
    sh_c = sh_bids = sh_tail = sh_cov = None
    if paged:
        if chunked:
            held = jnp.sum((state.kv.tbl >= 0).astype(jnp.int32), axis=1)
            if sharing:
                # read-only longest-prefix probe (weak entries — the pool
                # is untouched until the attach below incref's the hits)
                sh_c, sh_bids, sh_tail, sh_cov = cache_lookup(
                    state.kv.cache, state.kv.pool, bl.ph, bl.prompt_len,
                    block_size)
                # POST-DIVERGENCE demand: the covered blocks are free to
                # attach (incref only) — admission pays for fresh blocks
                # past the divergence point alone
                demand = shared_first_chunk_demand(
                    bl.prompt_len, sh_cov, chunk, block_size)
                commit_demand = _block_demand(bl, block_size) - sh_c
                # a row whose private demand exceeds the whole pool can
                # NEVER be granted at the current coverage: skip it in the
                # FCFS prefix (it must not dam later rows) but keep it
                # live/stalled — future re-registration can resurrect it
                NB = state.kv.pool.gen.shape[0]
                doomed = commit_demand > NB
                cow_a, held_free_a = _share_flags(
                    state.kv.tbl, state.kv.pool.refcnt, state.slots.busy,
                    state.slots.pos, state.slots.plen, held, block_size)
                rem = (_slot_rem(state.slots, held, block_size)
                       + jnp.where(cow_a, 1, 0))
                held_cover = held_free_a
            else:
                demand = first_chunk_demand(bl.prompt_len, chunk,
                                            block_size)
                commit_demand = _block_demand(bl, block_size)
                rem = _slot_rem(state.slots, held, block_size)
                held_cover = held
            headroom = block_headroom(
                rem, held_cover,
                banker_order(rem, state.slots.prio_r, state.slots.prio_k,
                             state.slots.busy),
                state.slots.busy)
            # commitment watermark: lifetime demand admits only into the
            # UNCOMMITTED budget (pipelined, unlike up-front — see
            # block_gate); the bootstrap flag keeps over-watermark
            # requests servable (alone, strict FCFS)
            total_rem = jnp.sum(rem)
            commit_free = commit - total_rem
            bootstrap = total_rem == 0
        else:
            demand = _block_demand(bl, block_size)
            headroom = jnp.int32(0)
            commit_demand, commit_free, bootstrap = None, 0, False

        def _gate(args):
            qos, admitted, _ = args
            eligible = (admitted & ~doomed) if sharing else admitted
            granted = block_gate(eligible, demand,
                                 _fcfs_key(bl, qos.grant, eligible),
                                 pool_free_count(state.kv.pool), headroom,
                                 commit_demand, commit_free, bootstrap)
            stalled = admitted & ~granted
            return (qos._replace(consumed=qos.consumed - segment_counts(
                bl.tenant, stalled, qos.ticket.shape[0])), granted,
                jnp.sum(stalled.astype(jnp.int32)))

        qos, admitted, n_stall = jax.lax.cond(
            jnp.any(admitted), _gate, lambda a: a,
            (qos, admitted, jnp.int32(0)))
    else:
        n_stall = jnp.int32(0)
    rno = state.round_no
    bl = bl._replace(
        valid=alive & ~admitted & ~expired,
        admit_round=jnp.where(admitted, rno, bl.admit_round),
        expire_round=jnp.where(expired, rno, bl.expire_round))
    state = state._replace(qos=qos, backlog=bl)

    # (3) slot assignment (FCFS → ascending free slots)
    state, rows, assign, tgt = _assign_slots(
        state, admitted, chunked, cov=sh_cov if sharing else None)
    n_hits = jnp.int32(0)
    if sharing:
        # (3a) attach the cache-covered prefix: seed the matched block ids
        # into the fresh slots' tables and incref each — no counter moves,
        # no pokes, no prefill flops for the covered tokens (`pool_incref`
        # is the conditional-post mapping's free half)
        kv = state.kv
        MB = kv.tbl.shape[1]
        Wc = min(sh_bids.shape[1], MB)
        bids_r = sh_bids[rows][:, :Wc]             # (S, Wc) — -1 beyond c
        tail_r = sh_tail[rows]                     # (S,)
        c_r = sh_c[rows]
        jW = jnp.arange(Wc, dtype=jnp.int32)
        col_ok = assign[:, None] & (bids_r >= 0)
        tgt_rows = jnp.where(assign, tgt, S)
        tbl = kv.tbl.at[jnp.where(col_ok, tgt_rows[:, None], S),
                        jW[None, :]].set(bids_r, mode="drop")
        tail_ok = assign & (tail_r >= 0)
        tbl = tbl.at[jnp.where(tail_ok, tgt, S),
                     jnp.clip(c_r, 0, MB - 1)].set(tail_r, mode="drop")
        pool = pool_incref(
            kv.pool,
            jnp.concatenate([bids_r, tail_r[:, None]], axis=1),
            jnp.concatenate([col_ok, tail_ok[:, None]], axis=1))
        state = state._replace(kv=KVPool(pool=pool, tbl=tbl,
                                         cache=kv.cache))
        # a fully-covered admit starts decode-ready: zero prefill flops
        n_hits = jnp.sum((assign & (sh_cov[rows] >= bl.prompt_len[rows])
                          & (bl.prompt_len[rows] > 0)).astype(jnp.int32))
    if paged and not chunked:
        # wrap-safe semaphore take of each granted slot's demand: ids pop
        # off the circular free queue at the ticket cursor in slot order
        # (cond-skipped when nothing was assigned — alloc of 0 is identity)
        def _alloc(kv):
            counts = jnp.zeros((S,), jnp.int32).at[tgt].set(
                jnp.where(assign, demand[rows], 0), mode="drop")
            pool, ids = pool_alloc(kv.pool, counts, kv.tbl.shape[1])
            return KVPool(pool=pool,
                          tbl=jnp.where(counts[:, None] > 0, ids, kv.tbl),
                          cache=kv.cache)

        state = state._replace(kv=jax.lax.cond(
            jnp.any(assign), _alloc, lambda kv: kv, state.kv))

    # (3b) chunked prefill: plan chunks/budget, take blocks incrementally
    # (newly admitted slots request their FIRST chunk right here — the
    # blocks the gate's headroom check just promised), park the stalled.
    n_cow = jnp.int32(0)
    chunk_ev = None
    if chunked:
        state, emit, n_cow, chunk_ev = _chunk_phase(state, chunk, budget,
                                                    block_size)
    if admit_fn is not None:  # in-graph prefill for newly admitted slots
        model = admit_fn(model, state, rows, assign, tgt)

    # (4) decode + sample every decode-ready slot (including this round's
    # admits in up-front mode — the host engine prefills then decodes
    # admitted rows the same step; in chunked mode a slot decodes from the
    # round AFTER its prefill completes, and parked slots skip the round)
    sl = state.slots
    if not chunked:
        emit = sl.busy
    toks, model = token_fn(model, state)
    toks = jnp.where(emit, jnp.asarray(toks, jnp.int32), sl.token)
    adv = emit.astype(jnp.int32) + (sl.chunk if chunked else 0)
    pos_old = sl.pos
    sl = sl._replace(token=toks,
                     emitted=sl.emitted + emit.astype(jnp.int32),
                     pos=sl.pos + adv,
                     # watchdog clock: any forward motion (token emitted
                     # or prefill chunk landed) re-arms the slot
                     last_adv=jnp.where(adv > 0, rno, sl.last_adv))
    if sharing:
        # (4b) publish prefixes at prefill COMPLETION: a slot whose cursor
        # crossed plen this round registers one weak entry per full block
        # boundary plus its partial tail (serving.prefix.cache_register —
        # identity on an empty mask, cond-skipped at runtime; the host
        # mirrors the same jitted call on its replica)
        completed = sl.busy & (sl.pos >= sl.plen) & (pos_old < sl.plen)
        kvr = state.kv
        cache = jax.lax.cond(
            jnp.any(completed),
            lambda c: cache_register(c, kvr.pool, sl.ph, sl.plen, kvr.tbl,
                                     completed, block_size),
            lambda c: c, kvr.cache)
        state = state._replace(kv=KVPool(pool=kvr.pool, tbl=kvr.tbl,
                                         cache=cache))

    # (5) completion: done slots post back; their units bank for the NEXT
    # round (the host engine's `_qos_free` in kernel mode)
    n_busy = jnp.sum(sl.busy.astype(jnp.int32))
    fin = sl.busy & (sl.emitted >= sl.max_new)
    n_fin = jnp.sum(fin.astype(jnp.int32))
    finrow = sl.row
    sl = sl._replace(busy=sl.busy & ~fin, row=jnp.where(fin, -1, sl.row))
    state = state._replace(
        slots=sl, slot_sema=post_batch(state.slot_sema, n_fin),
        free=leftover + n_fin, round_no=rno + 1)
    if paged:
        # completed slots post their blocks back AFTER decode — banked for
        # the NEXT round's gate, exactly the slot-unit completion timing
        state = state._replace(kv=jax.lax.cond(
            jnp.any(fin), lambda kv: KVPool(
                pool=pool_release(kv.pool, kv.tbl, fin),
                tbl=jnp.where(fin[:, None], -1, kv.tbl),
                cache=kv.cache),
            lambda kv: kv, state.kv))
    # (6) telemetry: append this round's end-of-round probe set to the
    # in-scan ring — same donated carry, zero extra host syncs.  Every
    # field must stay mirrorable from the host `step()` bookkeeping (the
    # bit-identity property of tests/test_obs.py) — extend both or
    # neither.
    if state.ring is not None:
        from .events import (EV_ADMIT, EV_COW, EV_FINISH, EV_PARK,
                             EV_PREEMPT, EV_PREFILL_CHUNK,
                             EV_PREFIX_ATTACH, EV_RESUME)
        from .sentinels import round_health

        parked_mask = sl.busy & sl.parked
        E = state.ring.buf.ev_kind.shape[1]
        if E:
            assert E == 8 * S, "event table must be 8 segments of S lanes"
            # the fixed per-round event table: 8 phase-major segments of S
            # lane-ascending entries (serving.events.SCAN_SEGMENTS), then
            # ONE stable compaction (valid entries first, order kept) so
            # the drained list equals the host step()'s per-kind appends
            lane = jnp.arange(S, dtype=jnp.int32)
            zb, zi = jnp.zeros((S,), bool), jnp.zeros((S,), jnp.int32)
            admit_uid, admit_arg = bl.rid[rows], bl.prompt_len[rows]
            if sharing:
                att_mask = assign & (sh_cov[rows] > 0)
                att_arg = sh_cov[rows]
            else:
                att_mask, att_arg = zb, zi
            ck = chunk_ev if chunk_ev is not None else {
                "park": zb, "park_arg": zi, "resume": zb,
                "chunk_tok": zi, "cow": zb, "cow_old": zi}
            segs = (
                (EV_PREEMPT, pre, pre_uid, lane, pre_arg),
                (EV_ADMIT, assign, admit_uid, tgt, admit_arg),
                (EV_PREFIX_ATTACH, att_mask, admit_uid, tgt, att_arg),
                (EV_PARK, ck["park"], sl.rid, lane, ck["park_arg"]),
                (EV_RESUME, ck["resume"], sl.rid, lane, zi),
                (EV_PREFILL_CHUNK, ck["chunk_tok"] > 0, sl.rid, lane,
                 ck["chunk_tok"]),
                (EV_COW, ck["cow"], sl.rid, lane, ck["cow_old"]),
                (EV_FINISH, fin, sl.rid, lane, sl.emitted),
            )
            evm = jnp.concatenate([m for _, m, _, _, _ in segs])
            kinds = jnp.concatenate(
                [jnp.full((S,), k, jnp.int32) for k, _, _, _, _ in segs])
            uids = jnp.concatenate(
                [u.astype(jnp.int32) for _, _, u, _, _ in segs])
            eslots = jnp.concatenate(
                [t.astype(jnp.int32) for _, _, _, t, _ in segs])
            eargs = jnp.concatenate(
                [a.astype(jnp.int32) for _, _, _, _, a in segs])
            order = jnp.argsort(~evm, stable=True)
            ev_n = jnp.sum(evm.astype(jnp.int32))
            keep = jnp.arange(E, dtype=jnp.int32) < ev_n
            ev_kind = jnp.where(keep, kinds[order], 0)
            ev_uid = jnp.where(keep, uids[order], -1)
            ev_slot = jnp.where(keep, eslots[order], -1)
            ev_arg = jnp.where(keep, eargs[order], 0)
        else:  # ring built without an event table: empty columns
            ze = jnp.zeros((0,), jnp.int32)
            ev_n, ev_kind, ev_uid, ev_slot, ev_arg = (jnp.int32(0), ze,
                                                      ze, ze, ze)
        sample = TelemetrySample(
            round_no=rno,
            now=now,
            admits=jnp.sum(admitted.astype(jnp.int32)),
            expires=jnp.sum(expired.astype(jnp.int32)),
            preempts=n_pre,
            tokens=jnp.sum(emit.astype(jnp.int32)),
            prefill_tokens=jnp.sum(sl.chunk),
            prefill_chunks=jnp.sum((sl.chunk > 0).astype(jnp.int32)),
            prefill_pending=pending_prompt_tokens(sl.pos, sl.plen, sl.busy),
            gate_stalls=n_stall,
            parked=jnp.sum(parked_mask.astype(jnp.int32)),
            backlog=jnp.sum(state.backlog.valid.astype(jnp.int32)),
            active=jnp.sum(sl.busy.astype(jnp.int32)),
            slot_free=_sdist(state.slot_sema.grant, state.slot_sema.ticket),
            kv_free=(pool_free_count(state.kv.pool) if paged
                     else jnp.int32(0)),
            kv_pokes=(jnp.sum(state.kv.pool.sema.bucket_seq,
                              dtype=jnp.uint32) if paged
                      else jnp.uint32(0)),
            prefix_hits=n_hits if sharing else jnp.int32(0),
            blocks_shared=(jnp.sum((state.kv.pool.refcnt >= 2)
                                   .astype(jnp.int32)) if sharing
                           else jnp.int32(0)),
            cow_copies=n_cow if sharing else jnp.int32(0),
            health=round_health(state, model, rno, block_size=block_size,
                                chunked=chunked, watchdog=watchdog),
            credit=_sdist(state.qos.grant, state.qos.consumed),
            poke_dead=state.qos.dead,
            kv_wait_hist=bucket_histogram(
                sl.park_bucket, parked_mask,
                state.ring.buf.kv_wait_hist.shape[1]),
            ev_n=ev_n, ev_kind=ev_kind, ev_uid=ev_uid, ev_slot=ev_slot,
            ev_arg=ev_arg)
        state = state._replace(ring=ring_append(state.ring, sample))
    ys = RoundOut(tokens=toks, emit=emit, fin=fin, pre=pre, row=finrow,
                  prerow=prerow,
                  n_live=jnp.sum(alive.astype(jnp.int32)),
                  # busy (not emit): chunked rounds that only prefill or
                  # park still count as engine activity, mirroring the
                  # host loop's "active dict non-empty" accounting (in the
                  # up-front modes emit == busy, so nothing changes)
                  n_active=n_busy)
    return state, model, ys


def megastep_scan(state: EngineState, model, nows, *, token_fn: TokenFn,
                  admit_fn: AdmitFn = None, admit_impl=None,
                  block_size: int = 0, chunk: int = 0, budget: int = 0,
                  commit: int = 0, watchdog: int = 0):
    """K fused engine rounds as one `lax.scan` — K host round-trips become
    one launch + one drain.  ``nows``: (K,) f32 epoch-relative timestamps
    (the host projects them at launch; in-graph time never advances on its
    own).  With ``chunk > 0`` every scanned round co-schedules chunked
    prefill with decode (zero extra host syncs for long prompts).
    Returns ``(state', model', RoundOut-of-(K, S) arrays)``."""

    def body(carry, now):
        st, m = carry
        st, m, ys = engine_round(st, m, now, token_fn=token_fn,
                                 admit_fn=admit_fn, admit_impl=admit_impl,
                                 block_size=block_size, chunk=chunk,
                                 budget=budget, commit=commit,
                                 watchdog=watchdog)
        return (st, m), ys

    (state, model), ys = jax.lax.scan(body, (state, model), nows)
    return state, model, ys


@functools.partial(jax.jit, static_argnames=("token_fn", "admit_fn",
                                             "admit_impl", "block_size",
                                             "chunk", "budget", "commit",
                                             "watchdog"),
                   donate_argnums=(0, 1))
def megastep_jit(state: EngineState, model, nows, *, token_fn: TokenFn,
                 admit_fn: AdmitFn = None, admit_impl=None,
                 block_size: int = 0, chunk: int = 0, budget: int = 0,
                 commit: int = 0, watchdog: int = 0):
    """Donated-jit entry: the EngineState and model pytrees are donated, so
    steady-state serving re-uses their device buffers across megasteps
    instead of reallocating per launch."""
    return megastep_scan(state, model, nows, token_fn=token_fn,
                         admit_fn=admit_fn, admit_impl=admit_impl,
                         block_size=block_size, chunk=chunk, budget=budget,
                         commit=commit, watchdog=watchdog)


def fused_round_impl(state, tenant_ids, tickets, alive, deadlines, now,
                     free_units, max_units):
    """Admission-round impl routing through the fused Pallas pass
    (`kernels.qos_admission.qos_round_fused`) — bit-identical to the
    functional default; the scheduler selects it on TPU backends where
    the kernel compiles natively inside the scan."""
    from ..kernels.ops import use_interpret
    from ..kernels.qos_admission import qos_round_fused

    return qos_round_fused(state, tenant_ids, tickets, alive, deadlines,
                           now, free_units, max_units=max_units,
                           interpret=use_interpret())


# --------------------------------------------------------------- models ----


def rid_token_fn(model, state: EngineState):
    """Deterministic request-identity token stream (oracle/testing): token
    = rid·1000 + #already-emitted — slot-assignment invariant, so the host
    loop and the megastep must produce byte-equal streams."""
    return state.slots.rid * 1000 + state.slots.emitted, model


def zero_token_fn(model, state: EngineState):
    """The default toy model: every slot samples token 0 (the host path's
    zero logits, zero sample)."""
    return jnp.zeros_like(state.slots.token), model


def make_paged_pool_model(key, vocab: int, d: int, num_blocks: int,
                          block_size: int):
    """Single-layer attention LM over the SHARED block-paged KV pool: KV
    lives in (NB, BS) pool blocks owned by the TWA block semaphore; which
    slot reads/writes which block is entirely the engine's block tables
    (`EngineState.kv.tbl`)."""
    k1, k2 = jax.random.split(key)
    return {
        "emb": jax.random.normal(k1, (vocab, d), jnp.float32) * 0.05,
        "wo": jax.random.normal(k2, (d, d), jnp.float32) * 0.05,
        "kp": jnp.zeros((num_blocks, block_size, 1, d), jnp.float32),
        "vp": jnp.zeros((num_blocks, block_size, 1, d), jnp.float32),
    }


def paged_pool_admit_fn(model, state: EngineState, rows, mask, slots):
    """In-graph prefill into the pool: the admitted rows' prompt embeddings
    scatter into the blocks their slots were just granted (token j of a
    slot lands in block ``tbl[slot, j // BS]`` offset ``j % BS``) — one
    bulk masked scatter per round for ALL admitted slots."""
    bl = state.backlog
    tbl = state.kv.tbl
    NB, BS = model["kp"].shape[:2]
    P = bl.prompt.shape[1]
    S = slots.shape[0]
    ptoks = bl.prompt[rows]                        # (S, P)
    plens = bl.prompt_len[rows]                    # (S,)
    pe = model["emb"][ptoks]                       # (S, P, d)
    j = jnp.arange(P, dtype=jnp.int32)
    stbl = tbl[jnp.where(mask, slots, 0)]          # (S, MB)
    bid = jnp.take_along_axis(
        stbl, jnp.broadcast_to((j // BS)[None, :], (S, P)), axis=1)
    valid = mask[:, None] & (j[None, :] < plens[:, None]) & (bid >= 0)
    bsel = jnp.where(valid, bid, NB)               # out-of-range → dropped
    off = jnp.broadcast_to((j % BS)[None, :], (S, P))
    return {
        **model,
        "kp": model["kp"].at[bsel, off, 0].set(pe, mode="drop"),
        "vp": model["vp"].at[bsel, off, 0].set(pe, mode="drop"),
    }


def paged_pool_token_fn(model, state: EngineState):
    """Pool-paged single-token decode: write the current token's KV into
    the slot's cursor block, attend over the slot's table-gathered blocks,
    and greedy-sample.  The in-graph attention is the VECTORIZED dense
    view of the table (`kernels.ref.paged_gather_kv` — the gathered width
    is the per-slot table, ∝ the slot's worst-case demand, never the pool
    or a global ring); the Pallas kernel `kernels/paged_decode` is the
    TPU path that additionally skips unwritten tail blocks in HBM (its
    sequential-row oracle `ref.paged_decode_ref` exists for bit-exactness,
    not for in-scan throughput)."""
    from ..kernels.ref import decode_attention_ref, paged_gather_kv

    sl = state.slots
    kv = state.kv
    NB, BS = model["kp"].shape[:2]
    S, MB = kv.tbl.shape
    cur = model["emb"][sl.token]                   # (S, d)
    rows_i = jnp.arange(S, dtype=jnp.int32)
    col = jnp.clip(sl.pos // BS, 0, MB - 1)
    bid = kv.tbl[rows_i, col]                      # current write block
    wr = sl.busy & (bid >= 0)
    bsel = jnp.where(wr, bid, NB)
    off = sl.pos % BS
    kp = model["kp"].at[bsel, off, 0].set(cur, mode="drop")
    vp = model["vp"].at[bsel, off, 0].set(cur, mode="drop")
    lens = jnp.where(sl.busy, sl.pos + 1, 0)       # attend incl. current
    kd, kpos = paged_gather_kv(kp, kv.tbl, lens)
    vd, _ = paged_gather_kv(vp, kv.tbl, lens)
    o = decode_attention_ref(cur[:, None, :], kd, vd, kpos,
                             jnp.maximum(lens - 1, 0))  # (S, 1, d)
    logits = (o[:, 0] @ model["wo"]) @ model["emb"].T
    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return toks, {**model, "kp": kp, "vp": vp}


def make_chunked_prefill_token_fn(chunk: int):
    """Factory: a `chunked_prefill_token_fn` whose chunk-scatter window is
    the STATIC chunk size instead of the whole prompt width — at most
    ``chunk`` prompt tokens can move per round, so the masked gather/
    scatter shrinks from (S, P) to (S, chunk) (~P/chunk× less per-round
    prefill-write work for long prompts).  ``chunk`` must be ≥ the
    engine's configured chunk size (a narrower window would silently
    drop the tail of every scheduled chunk) — the scheduler validates
    this via the ``_chunk_window`` attribute stamped here.  Create ONCE
    per engine and reuse — the returned closure's identity keys the
    megastep jit cache."""
    def token_fn(model, state):
        return _chunked_prefill_step(model, state, chunk)
    token_fn._chunk_window = chunk
    return token_fn


def chunked_prefill_token_fn(model, state: EngineState):
    """Continuous chunked prefill over the SHARED block pool — the in-scan
    path that lets ``megastep(K)`` serve prompts far longer than the
    one-shot prefill table with ZERO extra host syncs: each scanned round
    writes this round's prompt chunks (``slots.chunk`` tokens starting at
    the slot's KV cursor, planned by `serving.prefill.chunk_plan` into the
    blocks the round just took) and decodes every decode-ready slot —
    prefill and decode co-scheduled in ONE model call per round.

    Uses `make_paged_pool_model` state.  The chunk scatter is masked over
    the slot prompt width (shape-stable for any chunk size; use
    :func:`make_chunked_prefill_token_fn` to shrink the window to the
    engine's static chunk); the Pallas path for real models — blockwise
    flash-prefill with causal chunk attention and in-pass KV writeback —
    is `kernels/paged_prefill` (oracle-bit-exact standalone; see
    tests/test_paged_prefill.py).  Decode math is identical to
    `paged_pool_token_fn`, so token streams are bit-identical to one-shot
    prefill for ANY chunk size (property-tested in
    tests/test_chunked_prefill.py)."""
    return _chunked_prefill_step(model, state, state.slots.prompt.shape[1])


def _chunked_prefill_step(model, state: EngineState, window: int):
    from ..kernels.ref import decode_attention_ref, paged_gather_kv

    sl = state.slots
    kv = state.kv
    NB, BS = model["kp"].shape[:2]
    S, MB = kv.tbl.shape
    P = sl.prompt.shape[1]
    W = min(window, P)
    # ---- prefill: scatter this round's chunk embeddings into the pool
    j = sl.pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]   # (S, W)
    valid = jnp.arange(W, dtype=jnp.int32)[None, :] < sl.chunk[:, None]
    ptok = jnp.take_along_axis(sl.prompt, jnp.clip(j, 0, P - 1), axis=1)
    pe = model["emb"][ptok]                                         # (S, W, d)
    bid = jnp.take_along_axis(kv.tbl, jnp.clip(j // BS, 0, MB - 1), axis=1)
    ok = valid & (bid >= 0)
    bsel = jnp.where(ok, bid, NB)                 # out-of-range → dropped
    kp = model["kp"].at[bsel, j % BS, 0].set(pe, mode="drop")
    vp = model["vp"].at[bsel, j % BS, 0].set(pe, mode="drop")
    # ---- decode: `paged_pool_token_fn` math, decode-ready slots only
    # (prefilling and block-parked slots are masked; the engine's emit
    # mask drops their garbage samples the same way)
    ready = sl.busy & (sl.pos >= sl.plen)
    cur = model["emb"][sl.token]                                    # (S, d)
    rows_i = jnp.arange(S, dtype=jnp.int32)
    dbid = kv.tbl[rows_i, jnp.clip(sl.pos // BS, 0, MB - 1)]
    wr = ready & (dbid >= 0)
    if sl.cow_src is not None:
        # sharing: NEVER write a block another slot can read — a slot
        # whose copy-on-write take was denied this round still points at
        # the shared tail (the engine's emit mask already drops its
        # sample; this drops its KV write too)
        wr = wr & (kv.pool.refcnt[jnp.clip(dbid, 0, NB - 1)] <= 1)
    dbsel = jnp.where(wr, dbid, NB)
    if sl.cow_src is not None:
        # copy-on-write: a slot granted a private replacement for its
        # shared tail this round copies the whole shared block into it
        # BEFORE its decode write lands (the source stays intact this
        # round even if its refcount just hit zero — freed ids cannot be
        # re-granted before the NEXT round's alloc).  dbid IS the fresh
        # private block: the write cursor sits inside the replaced column.
        do_cow = wr & (sl.cow_src >= 0)
        csel = jnp.where(do_cow, dbid, NB)
        src = jnp.clip(sl.cow_src, 0, NB - 1)
        kp = kp.at[csel].set(kp[src], mode="drop")
        vp = vp.at[csel].set(vp[src], mode="drop")
    kp = kp.at[dbsel, sl.pos % BS, 0].set(cur, mode="drop")
    vp = vp.at[dbsel, sl.pos % BS, 0].set(cur, mode="drop")
    lens = jnp.where(wr, sl.pos + 1, 0)
    kd, kpos = paged_gather_kv(kp, kv.tbl, lens)
    vd, _ = paged_gather_kv(vp, kv.tbl, lens)
    o = decode_attention_ref(cur[:, None, :], kd, vd, kpos,
                             jnp.maximum(lens - 1, 0))              # (S, 1, d)
    logits = (o[:, 0] @ model["wo"]) @ model["emb"].T
    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return toks, {**model, "kp": kp, "vp": vp}
