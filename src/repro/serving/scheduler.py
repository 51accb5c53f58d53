"""FCFS continuous-batching serving scheduler — the paper's semaphore as the
admission-control core of an inference engine.

Resource model: the engine owns S decode slots (rows of the batched KV
cache).  Admission is a ticket semaphore with `grant` preloaded to S:

  * a new request `take`s → its ticket IS its global admission number; the
    FCFS guarantee of the paper becomes the engine's fairness guarantee
    (no request starves behind later arrivals — the pthread-baseline
    equivalent would let short prompts barge past long-queued ones);
  * when a sequence finishes, its slot frees → `post` advances grant, which
    enables exactly the next ticket(s) in line;
  * the TWA waiting array is what makes the *scheduler loop* scale: pending
    requests are dispersed over hashed buckets; each loop iteration
    re-examines ONLY requests whose bucket was poked by a post
    (`woken_mask`), instead of rescanning the whole backlog — the
    global-spinning analogue the paper eliminates.  With a 10k-deep backlog
    and 8 slots freed, the loop touches ~8 requests, not 10k.
  * host-side waiting uses the L1 TWA futex semaphore so request threads
    block politely (client-facing synchronous API), while the batched
    in-graph admission uses core.functional / kernels.sema_batch.

Multi-tenant QoS mode (``tenants={tenant_id: weight}``): admission routes
through `admission.functional_qos` — per-tenant functional TWA semaphores
replenished from the global slot pool by stride scheduling, one shared
bucket array gating which tenant queues the loop re-examines, and
deadline-expired backlog entries tombstoned so they never block later
live tickets (the skip-aware grant of the tombstone protocol).  FCFS holds
within a tenant; across tenants admission shares converge to the weights
under saturation.  With ``use_kernel=True`` the whole tenant round
(expire → replenish → admit → reclaim) runs as the fused Pallas pass
(`kernels.qos_admission`, interpret-mode off-TPU) instead of the host
queue walk — same admission semantics, one vectorized in-graph sweep.

Device-resident megastep (``megastep(K)``): the whole engine loop — deadline
preemption, the QoS admission round, TWA slot assignment, decode+sample,
completion — runs as ONE jitted `lax.scan` over a donated on-device
`serving.engine_state.EngineState` pytree, draining K decoded tokens per
host sync instead of one.  Round-for-round identical to K `step()` calls
(tests/test_megastep.py).

The engine below is deliberately model-agnostic: `step_fn` is any callable
(tokens, positions, caches) → (logits, caches); tests drive it with a tiny
transformer, examples/serve_continuous_batching.py with a reduced config.
"""

from __future__ import annotations

import heapq
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..admission.functional_qos import (
    make_qos,
    qos_reclaim,
    qos_replenish,
    qos_take,
)
from ..core.functional import (
    SemaState,
    make_block_pool,
    make_sema,
    next_pow2 as _next_pow2,
    pool_free_count,
    pool_incref,
    pool_release,
    pool_try_alloc,
    post_batch,
    take_batch,
    woken_mask,
)
from ..core.twa_semaphore import TWASemaphore
from ..obs.trace import count, span
from .events import (
    EV_ADMIT,
    EV_COW,
    EV_EXPIRE,
    EV_FINISH,
    EV_PARK,
    EV_PREEMPT,
    EV_PREFILL_CHUNK,
    EV_PREFIX_ATTACH,
    EV_QUARANTINE,
    EV_RESUME,
    EV_SUBMIT,
)
from .prefix import (
    cache_clear,
    cache_lookup,
    cache_register,
    make_prefix_cache,
    prompt_hashes,
)


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    tenant_id: str = "default"
    deadline: Optional[float] = None  # absolute time.monotonic admission deadline
    ticket: Optional[int] = None
    bucket: Optional[int] = None
    observed_seq: Optional[int] = None
    fast: bool = False  # admitted at take time (paper's fast-path return)
    slot: Optional[int] = None
    expired: bool = False  # deadline passed before admission (tombstoned)
    preempted: bool = False  # deadline passed mid-decode (slot reclaimed)
    out_tokens: list[int] = field(default_factory=list)
    done_event: threading.Event = field(default_factory=threading.Event)
    admit_t: float = 0.0
    # --- virtual-clock lifecycle stamps (repro.obs SLO tracking) ---
    # Keyed on the injectable ``clock=`` (NOT wall time), so TTFT/TPOT are
    # reproducible under virtual time and bit-identical between the host
    # loop and megastep drains (which stamp t0 + nows[k] per round).
    submit_clock: Optional[float] = None
    first_tok_clock: Optional[float] = None
    last_tok_clock: Optional[float] = None
    finish_clock: Optional[float] = None
    admit_round: int = -1  # global engine round of admission
    expire_round: int = -1  # global engine round of expiry/preemption
    # --- continuous chunked prefill (kv_pool + chunked_prefill engines) ---
    prefill_pos: int = 0  # prompt tokens prefilled so far
    kv_blocks: int = 0  # pool blocks currently held (incremental takes)
    prio_key: int = 0  # packed FCFS admission key (Banker order, secondary)
    parked: bool = False  # block-stalled on the block semaphore's waiting array
    park_bucket: int = 0  # observed TWAHash bucket (core.functional.park_state)
    park_seq: int = 0  # bucket sequence at park time
    # --- resilience (serving.sentinels / repro.resilience) ---
    last_adv_round: int = -1  # last engine round with forward progress
    #                           (host mirror of Slots.last_adv — the
    #                           stuck-slot watchdog's clock)
    retries: int = 0  # quarantine-requeue attempts consumed (recovery ladder)
    # --- prefix sharing (serving.prefix, prefix_cache= engines) ---
    ph: Optional[np.ndarray] = None  # (2, W+1) u32 prompt-hash table,
    #                                  computed ONCE at submit (the only
    #                                  place tokens are hashed — device
    #                                  and host both consume it as data)
    share: Optional[tuple] = None  # gate-time cache hit staged for the
    #                                attach: (c, bids, tail_bid, cov)


@dataclass
class EngineStats:
    admitted: int = 0
    finished: int = 0
    expired: int = 0  # deadline-missed (tombstoned tickets + preemptions)
    preempted: int = 0  # deadline-missed mid-decode (slot reclaimed)
    steps: int = 0
    backlog_scans: int = 0  # requests re-examined by the scheduler loop
    backlog_skipped: int = 0  # requests NOT re-examined thanks to TWA buckets
    wakeups: int = 0
    host_syncs: int = 0  # host rounds: 1 per step(), 1 per megastep();
    #                      not transfers (a step() blocks on several
    #                      device→host readbacks: the "d2h" phase count)
    kv_block_stalls: int = 0  # cumulative parked slot-rounds (block waits)
    prefill_chunks: int = 0  # prompt chunks written (chunked prefill)
    prefix_hits: int = 0  # admissions whose whole prompt was cache-covered
    #                       (zero prefill flops — prefix_cache= engines)
    cow_copies: int = 0  # copy-on-write block copies (diverging sharers)
    # --- recovery ladder (repro.resilience.recovery / serving.sentinels) ---
    quarantined: int = 0  # rung 1: sick slots evicted (blocks released)
    requeued: int = 0  # quarantined requests re-submitted after backoff
    kv_audits: int = 0  # rung 2: free-queue rebuilds from table ground truth
    kernel_fallbacks: int = 0  # rung 3: fused kernel → functional path
    snapshots: int = 0  # rung 4: EngineState checkpoints taken
    restores: int = 0  # rung 4: EngineState checkpoints restored


class ContinuousBatchingEngine:
    """Slot-synchronous decode engine with TWA-semaphore admission."""

    def __init__(
        self,
        step_fn: Callable,
        prefill_fn: Callable,
        n_slots: int,
        *,
        table_size: int = 256,
        use_kernel: bool = False,
        tenants: Optional[dict[str, float]] = None,
        clock: Callable[[], float] = time.monotonic,
        backlog_cap: int = 4096,
        prompt_cap: int = 32,
        kv_pool: Optional[tuple] = None,
        chunked_prefill: Optional[tuple] = None,
        prefix_cache: int = 0,
        obs=None,
        watchdog: int = 0,
    ):
        self.step_fn = step_fn
        self.prefill_fn = prefill_fn
        self.n_slots = n_slots
        self.sema = make_sema(count=n_slots, table_size=table_size)
        self.backlog: list[Request] = []  # pending (ticketed, not admitted)
        self.active: dict[int, Request] = {}  # slot → request
        self.free_slots = list(range(n_slots))
        self.stats = EngineStats()
        self._lock = threading.Lock()
        self._client_sem = TWASemaphore(0, waiting="futex")  # completion wakeups
        self._use_kernel = use_kernel
        # injectable clock: deadlines compare against THIS time source, so
        # tests (megastep ≡ host-loop property) can drive virtual time
        self._clock = clock
        self._round_no = 0  # global engine round counter (step & megastep)
        # --- observability (repro.obs) ---
        # ``obs=`` accepts anything with record_round(sample: dict) /
        # record_request(req) / summary() — normally `repro.obs.EngineObs`.
        # Per-round telemetry samples are produced by BOTH serving paths
        # with identical keys and values: host `step()` mirrors every probe
        # from its own bookkeeping, megastep drains the in-scan
        # TelemetryRing in its ONE host sync (engine_state.py docstring).
        self._obs = obs
        self._last_samples: list[dict] = []  # most recent step/megastep
        # --- request tracing (repro.obs.trace, PR 10) ---
        # Always-on bounded host-side trace buffer.  Engine-phase events
        # (ADMIT/PARK/…/FINISH) arrive via the per-round samples — emitted
        # by the in-scan event table on the megastep path and mirrored
        # bit-exactly by step()'s bookkeeping below; host-only lifecycle
        # events (SUBMIT/EXPIRE/QUARANTINE) are appended directly.  Pure
        # host writes: tracing adds ZERO device syncs.
        from ..obs.trace import TraceBuffer

        self._trace = TraceBuffer()
        # host step()'s per-round trace-event scratch, one list per
        # in-scan kind, appended in the device round's phase order and
        # flattened by `_host_sample` in the canonical segment order
        # (serving.events.SCAN_SEGMENTS)
        self._ev_preempt: list[list[int]] = []
        self._ev_admit: list[list[int]] = []
        self._ev_attach: list[list[int]] = []
        self._ev_park: list[list[int]] = []
        self._ev_resume: list[list[int]] = []
        self._ev_chunk: list[list[int]] = []
        self._ev_cow: list[list[int]] = []
        self._ev_finish: list[list[int]] = []
        # --- invariant sentinels (serving.sentinels) ---
        # ``watchdog=W``: the stuck-slot sentinel trips (H_STUCK in the
        # per-round health bitmask) when any busy slot makes no progress
        # for ≥ W consecutive rounds; 0 disables.  Both serving paths
        # evaluate it from the same clock (Slots.last_adv on device,
        # Request.last_adv_round on host) so the telemetry bit-identity
        # property covers the health field.
        self._watchdog = int(watchdog)
        # host H_NAN input: set when the host decode's logits (already on
        # host — never a hidden device sync) carry a NaN/Inf, or directly
        # by the fault injector (repro.resilience.faults); reset per round
        self._round_nonfinite = False
        # sticky variant: an injected model poison persists until a rung-4
        # restore repairs it (the device model stays NaN'd the same way),
        # so every round re-raises H_NAN until recovery clears this
        self._nonfinite_sticky = False
        self._now_r = 0.0  # clock() at step start (lifecycle stamps)
        # pure-host mirrors of the global slot semaphore's counters, so
        # `telemetry()` never touches device arrays (a hidden host sync
        # that `stats.host_syncs` would miss): takes bump the ticket,
        # finish-posts bump the grant — queue_depth = ticket − grant.
        self._sema_ticket_h = 0
        self._sema_grant_h = n_slots
        # per-round scratch the host sample mirrors from (reset each step)
        self._round_gate_stalls = 0
        self._round_prefill_tokens = 0
        self._round_prefill_chunks = 0
        self._round_prefix_hits = 0
        self._round_cow_copies = 0
        self._backlog_cap = backlog_cap  # megastep device backlog ceiling
        self._prompt_cap = prompt_cap  # megastep padded prompt ceiling
        self.megastep_model = None  # device model pytree (megastep mode)
        # --- block-paged KV pool (core.functional.BlockPool) ---
        # ``kv_pool=(num_blocks, block_size[, max_blocks_per_seq])``:
        # admission gates on BOTH a free slot and the request's worst-case
        # block demand (multi-resource admission); the host keeps only the
        # free-block COUNTER (bit-identical to the device semaphore's
        # grant − ticket by construction) — block identities live in the
        # device pool, so paged engines must decode via megastep.
        self._kv_pool = kv_pool
        # --- continuous chunked prefill (serving.prefill) ---
        # ``chunked_prefill=(chunk_tokens, token_budget)``: admission gates
        # on FIRST-CHUNK demand behind the reserved headroom, prompts
        # prefill up to chunk_tokens per round under the per-round prefill
        # token budget (Sarathi-style co-scheduling with decode), blocks
        # are taken incrementally at block-boundary crossings, and
        # block-stalled slots PARK on the block semaphore's waiting array
        # (resumed FCFS when releases poke their bucket) — see
        # serving/engine_state.py for the stall/park policy and the
        # no-deadlock headroom invariant.
        self._chunk, self._budget, self._kv_commit = 0, 0, 0
        if chunked_prefill is not None:
            if kv_pool is None:
                raise ValueError(
                    "chunked_prefill requires the block-paged pool "
                    "(kv_pool=...): chunks allocate pool blocks "
                    "incrementally")
            ch, bu, *cw = chunked_prefill
            if int(ch) < 1 or int(bu) < 1:
                raise ValueError(
                    f"chunked_prefill needs a positive chunk size and "
                    f"token budget, got {chunked_prefill}")
            self._chunk, self._budget = int(ch), int(bu)
            # optional third element: the commitment watermark in BLOCKS
            # (aggregate outstanding worst-case demand admission may keep
            # in flight).  Default 9/16 of the pool: a balance between
            # utilization (higher watermark ⇒ more resident sequences ⇒
            # more written blocks) and safety-chain slack (lower ⇒ fewer
            # parks serializing the endgame).
            self._kv_commit = int(cw[0]) if cw \
                else max(1, int(kv_pool[0]) * 9 // 16)
        if kv_pool is not None:
            if tenants is None:
                raise ValueError("kv_pool requires QoS mode (tenants=...)")
            nb, bs, *rest = kv_pool
            nb, bs = int(nb), int(bs)
            if nb <= 0 or (nb & (nb - 1)) or bs <= 0:
                raise ValueError(
                    f"kv_pool needs a power-of-two block count and a "
                    f"positive block size, got {kv_pool}")
            self._kv_blocks, self._kv_bs = nb, bs
            self._kv_mb = int(rest[0]) if rest else nb  # table width
            self._kv_free_blocks = nb
            self._kv_state = None  # persisted device KVPool across megasteps
            # host mirror of the device block semaphore (chunked mode):
            # takes advance ticket, releases post+poke, and the park/wake
            # buckets come off THIS state — table_size/salt must match
            # `engine_state.make_engine_state`'s pool (slot_table=64) so
            # host-loop and megastep runs observe identical bucket moves
            self._kv_sema = make_sema(count=nb, table_size=64)
        # --- refcounted prefix sharing (serving.prefix, PR 9) ---
        # ``prefix_cache=E`` (power-of-two entries; requires chunked
        # prefill) attaches the weak prompt-prefix cache: admissions whose
        # prompt prefix is already pool-resident attach the shared blocks
        # by `pool_incref` (zero prefill flops, zero new HBM) and pay
        # admission only for post-divergence demand.  Counters are no
        # longer a sufficient host mirror — block IDENTITIES and refcounts
        # decide sharing — so the host keeps a full replica of the device
        # pool (`_kv_hpool`/`_kv_htbl`/`_kv_cache`) and mutates it through
        # the SAME jitted functions the scanned round uses, in the SAME
        # batched call pattern (one release per preempt/cow/finish phase —
        # sequential per-request releases would reorder the free queue).
        self._kv_share = int(prefix_cache) > 0
        if self._kv_share:
            if not self._chunk:
                raise ValueError(
                    "prefix_cache requires continuous chunked prefill "
                    "(chunked_prefill=...): shared prefixes resume at the "
                    "divergence point mid-prompt")
            e = int(prefix_cache)
            if e & (e - 1):
                raise ValueError(
                    f"prefix_cache needs a power-of-two entry count "
                    f"(direct-mapped homes are key & (E-1)), got {e}")
            self._kv_prefix = e
            self._hash_w = self._prompt_cap // self._kv_bs
            self._kv_hpool = make_block_pool(self._kv_blocks, table_size=64)
            self._kv_cache = make_prefix_cache(e)
            self._kv_htbl = np.full((n_slots, self._kv_mb), -1, np.int32)
            self._kv_sema = self._kv_hpool.sema
            self._kv_refcnt_h = np.zeros(self._kv_blocks, np.int32)
        # --- multi-tenant QoS admission (admission.functional_qos) ---
        self._tenants = tenants
        if tenants is not None:
            # weight 0 is meaningful at the functional layer (at most one
            # unit, then the virtual pass saturates to +inf) but in a
            # serving engine it means silent starvation — reject it here.
            bad = {t: w for t, w in tenants.items() if not w > 0}
            if bad:
                raise ValueError(
                    f"tenant weights must be > 0, got {bad}; zero-weight "
                    "tenants would starve after at most one admission")
            self._tenant_names = list(tenants)
            self._tindex = {t: i for i, t in enumerate(self._tenant_names)}
            self.qos = make_qos([tenants[t] for t in self._tenant_names],
                                table_size=table_size)
            self._qos_free = n_slots  # undistributed global slots
            self._tenant_queues: list[deque[Request]] = [
                deque() for _ in self._tenant_names]
            self._tenant_live = np.zeros(len(self._tenant_names), np.int64)
            self.tenant_admitted = {t: 0 for t in self._tenant_names}
            self.tenant_expired = {t: 0 for t in self._tenant_names}
            self._deadline_heap: list[tuple[float, int, Request]] = []

    # ------------------------------------------------------------ client ----

    def submit(self, req: Request) -> Request:
        """Take a ticket (FCFS position) and enqueue."""
        if self._tenants is not None:
            self._submit_qos([req])
            return req
        with self._lock:
            req.submit_clock = self._clock()
            self._sema_ticket_h += 1
            state, tickets, admitted, buckets = take_batch(
                self.sema, jnp.ones((1,), bool)
            )
            self.sema = state
            req.ticket = int(tickets[0])
            req.bucket = int(buckets[0])
            req.fast = bool(admitted[0])
            req.observed_seq = int(self.sema.bucket_seq[req.bucket])
            self.backlog.append(req)
            self._trace.add(EV_SUBMIT, req.rid, -1, 0, req.submit_clock,
                            self._round_no)
        return req

    def submit_batch(self, reqs: list[Request]) -> None:
        """Vectorized ticket issuance — one fused pass for K arrivals (the
        sema_batch kernel path when enabled)."""
        if self._tenants is not None:
            self._submit_qos(reqs)
            return
        with self._lock:
            n = len(reqs)
            sclk = self._clock()
            self._sema_ticket_h += n
            if self._use_kernel:
                from ..kernels.ops import sema_batch as sema_kernel

                nt, ng, nseq, tk, adm, bkt, wok = sema_kernel(
                    self.sema.ticket, self.sema.grant, self.sema.bucket_seq,
                    jnp.ones((n,), bool), jnp.uint32(0), self.sema.salt,
                )
                self.sema = SemaState(nt, ng, nseq, self.sema.salt)
            else:
                self.sema, tk, adm, bkt = take_batch(self.sema, jnp.ones((n,), bool))
            for r, t, b, a in zip(reqs, np.asarray(tk), np.asarray(bkt), np.asarray(adm)):
                r.submit_clock = sclk
                r.ticket = int(t)
                r.bucket = int(b)
                r.fast = bool(a)
                r.observed_seq = int(self.sema.bucket_seq[r.bucket])
                self.backlog.append(r)
                self._trace.add(EV_SUBMIT, r.rid, -1, 0, sclk,
                                self._round_no)

    # ------------------------------------------------- multi-tenant (QoS) ---

    def _submit_qos(self, reqs: list[Request]) -> None:
        """Batched ticket issuance against the per-tenant QoS semaphores.
        Arrivals whose deadline already passed are dead on arrival."""
        unknown = {r.tenant_id for r in reqs} - self._tindex.keys()
        if unknown:
            raise ValueError(
                f"unregistered tenant(s) {sorted(unknown)}; this engine "
                f"serves tenants {list(self._tenant_names)}")
        if self._kv_pool is not None:
            # submit-time capacity check: a request whose WHOLE-LIFETIME
            # demand exceeds what the pool (or its slot table) can ever
            # hold would stall forever — in chunked mode it would be
            # admitted on its small first chunk and then park with a
            # deficit no amount of releases can cover, so it is rejected
            # here with a clear error instead.  Chunked demand uses the
            # UNTRUNCATED prompt (chunked prompts are never truncated);
            # this also closes the no-deadlock induction for newcomers
            # (engine_state.py: headroom invariant needs demand ≤ pool).
            cap = min(self._kv_mb, self._kv_blocks)
            for r in reqs:
                if self._chunk:
                    plen = len(r.prompt) or 1
                    if plen > self._prompt_cap:
                        raise ValueError(
                            f"request rid={r.rid} prompt ({plen} tokens) "
                            f"exceeds prompt_cap={self._prompt_cap}; "
                            "chunked prefill never truncates prompts — "
                            "raise prompt_cap")
                # the prompt_cap check above makes _kv_demand's truncation
                # a no-op in chunked mode: ONE demand formula everywhere
                # (host gate/headroom/chunk phase and the device paths all
                # reduce to it — the bit-identity mirror depends on that)
                if self._kv_share and r.ph is None:
                    # hash ONCE, here: the (2, W+1) u32 table rides the
                    # request as plain data (device lookups re-use it)
                    r.ph = np.asarray(
                        prompt_hashes(r.prompt or [0], self._kv_bs,
                                      self._hash_w), np.uint32)
                dem = self._kv_demand(r)
                if dem > cap:
                    if self._kv_share and dem <= self._kv_mb:
                        # post-divergence demand: blocks covered by a
                        # cached prefix attach by incref (zero pool
                        # demand) — a request over the raw pool size is
                        # still servable while its prefix stays resident.
                        # Weak entries can die later; the gate then holds
                        # it stalled (doomed) until re-registration
                        # resurrects the coverage.
                        plen = min(len(r.prompt), self._prompt_cap) or 1
                        c, _, _, cv = cache_lookup(
                            self._kv_cache, self._kv_hpool,
                            jnp.asarray(r.ph)[None],
                            jnp.asarray([plen], jnp.int32), self._kv_bs)
                        if dem - int(c[0]) <= self._kv_blocks:
                            continue
                    raise ValueError(
                        f"request rid={r.rid} needs {dem} KV blocks over "
                        f"its lifetime (> {cap} = min(table, pool)): "
                        f"prompt_len + max_new must fit "
                        f"{cap * self._kv_bs} pooled tokens — it could "
                        "never be served and would stall forever")
        with self._lock:
            now = self._clock()
            ids = [self._tindex[r.tenant_id] for r in reqs]
            # Deadlines enter the graph RELATIVE to now: small deltas stay
            # exact in float32, whereas absolute monotonic stamps (~boot
            # seconds) lose sub-second precision after weeks of uptime and
            # would misclassify short-deadline arrivals as dead-on-arrival.
            dls = [np.inf if r.deadline is None else r.deadline - now
                   for r in reqs]
            self.qos, tickets, buckets, expired = qos_take(
                self.qos, jnp.asarray(ids, jnp.int32),
                jnp.ones(len(reqs), bool), jnp.asarray(dls), 0.0)
            seq = np.asarray(self.qos.bucket_seq)
            for r, i, t, b, e in zip(reqs, ids, np.asarray(tickets),
                                     np.asarray(buckets), np.asarray(expired)):
                r.submit_clock = now
                self._trace.add(EV_SUBMIT, r.rid, -1, 0, now,
                                self._round_no)
                if e:
                    self._expire_req(r, i)
                    continue
                r.ticket = int(t)
                r.bucket = int(b)
                r.observed_seq = int(seq[r.bucket])
                r.fast = True  # fresh arrival: examine once on next pass
                self._tenant_queues[i].append(r)
                self._tenant_live[i] += 1
                # the kernel round re-evaluates every deadline in-graph each
                # step — the host expiry heap would only leak entries there
                if r.deadline is not None and not self._use_kernel:
                    heapq.heappush(self._deadline_heap, (r.deadline, r.rid, r))
            # Undistributed slots flow to the new demand immediately (the
            # work-conserving fast path of the hierarchy).
            self._replenish_qos(0)

    def _kv_demand(self, r: Request) -> int:
        """Worst-case block demand — MUST mirror the in-graph
        `engine_state._block_demand`: the device sees the prompt truncated
        to the padded cap, so the host clamps the same way."""
        plen = min(len(r.prompt), self._prompt_cap) or 1
        return max(1, -(-(plen + r.max_new_tokens) // self._kv_bs))

    def _kv_gate(self, cands: list[tuple[Request, int]]):
        """Host mirror of `admission.functional_qos.block_gate` + the
        in-graph `_fcfs_key`: of the QoS-admitted candidates, grant the
        longest FCFS prefix (wrap-safe clamped ticket distance from the
        post-round grant frontier, tenant-index tiebreak — byte-identical
        key arithmetic) whose cumulative block demand fits the free pool;
        strict FCFS, no bypass.  Up-front mode gates on worst-case demand
        and consumes it from the host counter; chunked mode gates on
        FIRST-CHUNK demand behind the reserved headroom
        (`functional_qos.block_headroom` — the no-deadlock invariant) and
        consumes nothing (blocks are taken incrementally by the chunk
        phase).  Returns (granted, stalled) index lists into ``cands``,
        both in gate order; granted requests get their Banker priority
        key stamped."""
        from .engine_state import _D_CLAMP, _T_BITS
        from .prefill import shared_first_chunk_demand

        grants = np.asarray(self.qos.grant)

        def key(i: int) -> int:
            r, tidx = cands[i]
            d = (r.ticket - int(grants[tidx])) & 0xFFFFFFFF
            d = d - (1 << 32) if d >= (1 << 31) else d
            return (max(-_D_CLAMP, min(_D_CLAMP, d)) << _T_BITS) + tidx

        order = sorted(range(len(cands)), key=key)
        free = self._kv_free_blocks
        commit_free = bootstrap = 0
        share = None
        if self._chunk:
            cow = hf = None
            if self._kv_share:
                cow, hf = self._kv_share_state()
                # read-only longest-prefix probe over the candidates (the
                # device gate's `cache_lookup` over the backlog) — demand
                # past the divergence point only
                pln = np.asarray(
                    [min(len(r.prompt), self._prompt_cap) or 1
                     for r, _ in cands], np.int32)
                ph = np.stack([np.asarray(r.ph, np.uint32)
                               for r, _ in cands]) if cands else \
                    np.zeros((0, 2, self._hash_w + 1), np.uint32)
                c_a, bids_a, tail_a, cov_a = cache_lookup(
                    self._kv_cache, self._kv_hpool, jnp.asarray(ph),
                    jnp.asarray(pln), self._kv_bs)
                dem_a = np.asarray(shared_first_chunk_demand(
                    jnp.asarray(pln), cov_a, self._chunk, self._kv_bs))
                c_a, bids_a = np.asarray(c_a), np.asarray(bids_a)
                tail_a, cov_a = np.asarray(tail_a), np.asarray(cov_a)
                commit_a = np.asarray(
                    [self._kv_demand(r) for r, _ in cands],
                    np.int64) - c_a
                # a row whose post-divergence demand exceeds the whole
                # pool can never be granted at current coverage: skip it
                # in the FCFS prefix (it must not dam later rows), keep
                # it stalled — re-registration can resurrect it
                doomed_a = commit_a > self._kv_blocks
                share = (c_a, bids_a, tail_a, cov_a, dem_a, commit_a,
                         doomed_a)
            free -= self._kv_headroom(share=(cow, hf))
            total_rem = sum(
                self._kv_rem(r) + (1 if cow is not None and cow[s] else 0)
                for s, r in self.active.items())
            commit_free = self._kv_commit - total_rem
            bootstrap = total_rem == 0
        granted, stalled = [], []
        dammed = False  # strict FCFS: first ELIGIBLE misfit blocks all
        for i in order:
            r = cands[i][0]
            if self._chunk:
                if share is not None:
                    if share[6][i]:  # doomed: skip, don't dam successors
                        stalled.append(i)
                        continue
                    dem = int(share[4][i])
                    commit = int(share[5][i])
                else:
                    dem = self._kv_first_chunk(r)
                    commit = self._kv_demand(r)
                ok = dem <= free and (commit <= commit_free
                                      or (bootstrap and not granted))
            else:
                dem = self._kv_demand(r)
                commit = 0
                ok = dem <= free
            if not dammed and ok:
                free -= dem
                commit_free -= commit
                r.prio_key = key(i)
                if share is not None:
                    r.share = (int(share[0][i]), share[1][i],
                               int(share[2][i]), int(share[3][i]))
                granted.append(i)
            else:
                dammed = True
                stalled.append(i)
        if not self._chunk:
            # up-front take: the host block-semaphore mirror's ticket
            # advances by the total granted demand — the exact counter move
            # the device `pool_alloc` makes at slot assignment, so
            # `telemetry`'s kv probes (and the megastep bit-identity
            # property) see the same semaphore state on both paths
            taken = self._kv_free_blocks - free
            self._kv_free_blocks = free
            if taken:
                self._kv_sema = self._kv_sema._replace(
                    ticket=self._kv_sema.ticket + jnp.uint32(taken))
        self._round_gate_stalls += len(stalled)
        return granted, stalled

    def _kv_first_chunk(self, r: Request) -> int:
        """First-chunk block demand — what chunked admission gates on
        (mirrors `serving.prefill.first_chunk_demand`)."""
        plen = min(len(r.prompt), self._prompt_cap) or 1
        return max(1, -(-min(self._chunk, plen) // self._kv_bs))

    def _kv_rem(self, r: Request) -> int:
        """Worst-case REMAINING block demand of an active request
        (`_kv_demand` minus the blocks already taken)."""
        return self._kv_demand(r) - r.kv_blocks

    def _kv_share_state(self):
        """Per-slot ``(cow, held_free)`` off the sharing replica — ONE
        call into the canonical `engine_state._share_flags` (host and
        device must never fork the formulas).  ``cow[s]``: the slot's
        next decode write lands in a still-shared tail block (it owes a
        private copy); ``held_free[s]``: blocks the slot alone references
        (the only Banker cover its release can fund)."""
        from .engine_state import _share_flags

        S = self.n_slots
        busy = np.zeros(S, bool)
        pos = np.zeros(S, np.int32)
        plen = np.zeros(S, np.int32)
        held = (self._kv_htbl >= 0).sum(axis=1).astype(np.int32)
        for s, r in self.active.items():
            pl = min(len(r.prompt), self._prompt_cap) or 1
            busy[s] = True
            pos[s] = (r.prefill_pos if r.prefill_pos < pl
                      else pl + len(r.out_tokens))
            plen[s] = pl
        cow, hf = _share_flags(
            jnp.asarray(self._kv_htbl), self._kv_hpool.refcnt,
            jnp.asarray(busy), jnp.asarray(pos), jnp.asarray(plen),
            jnp.asarray(held), self._kv_bs)
        return np.asarray(cow), np.asarray(hf)

    def _hshare_sync(self) -> None:
        """Re-derive every host counter mirror from the sharing replica
        after a pool mutation (the replica is the single source of truth
        in prefix_cache mode — `telemetry()` and `_host_sample` read
        these mirrors, never device arrays)."""
        self._kv_sema = self._kv_hpool.sema
        self._kv_free_blocks = int(pool_free_count(self._kv_hpool))
        self._kv_refcnt_h = np.asarray(self._kv_hpool.refcnt)

    def _hshare_release(self, slots: list[int]) -> None:
        """ONE batched decref of every block the given slots hold —
        mirroring the device round's single `pool_release` per phase
        (preempt / finish / quarantine).  Sequential per-slot releases
        would enqueue freed ids in a different free-queue order and
        diverge from the megastep path."""
        if not slots:
            return
        mask = np.zeros(self.n_slots, bool)
        mask[slots] = True
        self._kv_hpool = pool_release(
            self._kv_hpool, jnp.asarray(self._kv_htbl), jnp.asarray(mask))
        self._kv_htbl[mask] = -1
        self._hshare_sync()

    def _kv_headroom(self, share=None) -> int:
        """Host mirror of `functional_qos.block_headroom` over the
        nearest-completion safety chain (`prefill.banker_order`): the
        smallest free-pool level that keeps every active sequence's
        remaining worst-case demand covered by the pool plus what its
        chain-predecessors will release (see engine_state.py's
        headroom-invariant docs).  With prefix sharing, a pending
        copy-on-write still owes one block (rem + 1) and only
        privately-held blocks fund the cover (``held_free``, not the
        table count) — `serving.sentinels.round_health` applies the same
        generalization in-graph."""
        cow = hf = None
        if share is not None:
            cow, hf = share
        elif self._kv_share:
            cow, hf = self._kv_share_state()

        def rem_of(s: int, r: Request) -> int:
            return self._kv_rem(r) + (1 if cow is not None and cow[s]
                                      else 0)

        acts = sorted(self.active.items(),
                      key=lambda kv: (rem_of(*kv),
                                      kv[1].admit_round, kv[1].prio_key,
                                      kv[0]))
        cum = head = 0
        for s, r in acts:
            head = max(head, rem_of(s, r) - cum)
            cum += int(hf[s]) if hf is not None else r.kv_blocks
        return max(head, 0)

    def _fcfs_sort(self, reqs: list[Request]) -> None:
        """Sort admitted requests into wrap-safe admission order: signed
        ticket distance from the tenant's grant frontier (tickets are u32
        and may cross 2³²; raw comparison would order a post-wrap ticket
        before its predecessor).  Cross-tenant ordering is cosmetic — FCFS
        is a per-tenant invariant.  The grant snapshot is taken ONCE (one
        device→host transfer per round, not per request)."""
        count("d2h")
        grants = np.asarray(self.qos.grant)

        def key(r: Request):
            d = (r.ticket - int(grants[self._tindex[r.tenant_id]])) & 0xFFFFFFFF
            return (d - (1 << 32) if d >= (1 << 31) else d, r.tenant_id)

        reqs.sort(key=key)

    def _expire_req(self, r: Request, tidx: int) -> None:
        r.expired = True
        pre_rnd = r.expire_round  # megastep drain pre-stamps the in-scan
        r.expire_round = self._round_no  # round; host paths use this one
        self.stats.expired += 1
        self.tenant_expired[self._tenant_names[tidx]] += 1
        if r.finish_clock is None:  # megastep drains pre-stamp per-round
            r.finish_clock = self._clock()
        # backlog expiry is host-resolved on BOTH serving paths (heap pop
        # order vs row order has no canonical in-scan mirror), so its
        # trace terminal is a host-side event, never an in-scan one
        self._trace.add(EV_EXPIRE, r.rid, -1, 0, r.finish_clock,
                        pre_rnd if pre_rnd >= 0 else r.expire_round)
        self._obs_done(r)
        r.done_event.set()

    def _expire_due_qos(self) -> None:
        """Tombstone backlog entries whose admission deadline passed.  The
        host-side skip: the next live same-tenant waiter is flagged for
        re-examination so the dead ticket never blocks it."""
        now = self._clock()
        dead_bump = np.zeros(len(self._tenant_names), np.uint32)
        while self._deadline_heap and self._deadline_heap[0][0] <= now:
            _, _, r = heapq.heappop(self._deadline_heap)
            if r.expired or r.slot is not None or r.done_event.is_set():
                continue  # admitted or already resolved — deadline is moot
            tidx = self._tindex[r.tenant_id]
            self._expire_req(r, tidx)
            self._tenant_live[tidx] -= 1
            dead_bump[tidx] += 1
            for nxt in self._tenant_queues[tidx]:
                if not nxt.expired:  # successor inherits the wake
                    nxt.fast = True
                    break
        if dead_bump.any():
            self.qos = self.qos._replace(
                dead=self.qos.dead + jnp.asarray(dead_bump))
            # Credit stranded on tombstoned tickets re-enters the pool and
            # is re-granted to live demand (skip-aware replenishment).
            self._replenish_qos(0)

    def _admit_ready_qos_kernel(self) -> list[Request]:
        """Fused in-graph admission round (``use_kernel=True``): expire,
        weighted replenish, tombstone-transparent FCFS admit and reclaim run
        as ONE `kernels.qos_admission` pass over the whole backlog —
        O(N·S/block) vectorized work instead of the host-side queue walk
        (every row is examined, but in-graph; the TWA bucket gating of the
        host path is subsumed by the kernel's blocked live-rank sweep)."""
        from ..kernels.ops import qos_round as qos_round_kernel

        rows = [r for q in self._tenant_queues for r in q if not r.expired]
        if not rows:
            return []
        now = self._clock()
        ids = np.asarray([self._tindex[r.tenant_id] for r in rows], np.int32)
        tks = np.asarray([r.ticket for r in rows], np.uint32)
        # relative deadlines: see _submit_qos on float32 precision
        dls = np.asarray([np.inf if r.deadline is None else r.deadline - now
                          for r in rows], np.float32)
        state, admitted, expired, leftover = qos_round_kernel(
            self.qos, ids, tks, np.ones(len(rows), bool), dls, 0.0,
            self._qos_free, max_units=self.n_slots)
        self.qos = state
        count("d2h", 3)  # leftover, admitted and expired, read back here
        self._qos_free = int(leftover)
        self.stats.backlog_scans += len(rows)
        admitted = np.asarray(admitted).copy()
        expired = np.asarray(expired)
        if self._kv_pool is not None and admitted.any():
            # multi-resource gate: block-stalled rows lose their grant and
            # refund the tenant's slot credit (they stay queued and are
            # re-examined next round — the in-graph round does exactly
            # this via `block_gate` + the consumed refund)
            cidx = np.flatnonzero(admitted)
            _, stalled = self._kv_gate([(rows[i], int(ids[i])) for i in cidx])
            if stalled:
                bump = np.zeros(len(self._tenant_names), np.uint32)
                for i in stalled:
                    admitted[cidx[i]] = False
                    bump[ids[cidx[i]]] += 1
                    rows[cidx[i]].fast = True  # retry while blocks drain
                self.qos = self.qos._replace(
                    consumed=self.qos.consumed - jnp.asarray(bump))
        out: list[Request] = []
        for r, i, a, e in zip(rows, ids, admitted, expired):
            if e:
                self._expire_req(r, int(i))
                self._tenant_live[int(i)] -= 1
            elif a:
                self._tenant_live[int(i)] -= 1
                self.tenant_admitted[r.tenant_id] += 1
                out.append(r)
        if admitted.any() or expired.any():
            gone = {id(r) for r, a, e in zip(rows, admitted, expired) if a or e}
            for tidx, q in enumerate(self._tenant_queues):
                self._tenant_queues[tidx] = deque(
                    r for r in q if id(r) not in gone)
        self._fcfs_sort(out)
        return out

    def _admit_ready_qos(self) -> list[Request]:
        """Weighted-FCFS admission: per-tenant queues are re-examined only
        when their head's bucket was poked by a replenish (or flagged by an
        arrival/expiry) — the TWA gating at tenant granularity."""
        if self._use_kernel:
            return self._admit_ready_qos_kernel()
        self._expire_due_qos()
        # wrap-safe spendable credit: u32 difference reinterpreted signed
        # (mirrors functional_qos.avail's _sdist — a raw widened subtraction
        # would go hugely negative once grant crosses 2³²)
        avail = (np.asarray(self.qos.grant) - np.asarray(self.qos.consumed)
                 ).astype(np.int32).astype(np.int64)
        seq = np.asarray(self.qos.bucket_seq)
        admitted: list[Request] = []
        spent = np.zeros(len(self._tenant_names), np.uint32)
        for tidx, q in enumerate(self._tenant_queues):
            while q and q[0].expired:
                q.popleft()  # lazy removal of tombstoned heads
            if not q:
                continue
            head = q[0]
            if not (head.fast or seq[head.bucket] != head.observed_seq):
                self.stats.backlog_skipped += sum(not r.expired for r in q)
                continue
            head.fast = False
            head.observed_seq = int(seq[head.bucket])
            while q and avail[tidx] - int(spent[tidx]) > 0:
                r = q.popleft()
                if r.expired:
                    continue
                spent[tidx] += 1
                self._tenant_live[tidx] -= 1
                self.tenant_admitted[r.tenant_id] += 1
                admitted.append(r)
            # examined = the head + each admitted row; everything left in
            # the queue was never touched (the TWA skip).
            self.stats.backlog_scans += int(spent[tidx]) + (1 if q and q[0] is head else 0)
            self.stats.backlog_skipped += sum(not r.expired for r in q) \
                - (1 if q and q[0] is head else 0)
        if spent.any():
            self.qos = self.qos._replace(
                consumed=self.qos.consumed + jnp.asarray(spent))
        if self._kv_pool is not None and admitted:
            # multi-resource gate: roll the block-stalled suffix back onto
            # the queue heads (per tenant the stalled candidates are a
            # contiguous FIFO suffix — global FCFS preserves per-tenant
            # ticket order), refund their slot credit, and flag them for
            # re-examination once blocks drain
            cands = [(r, self._tindex[r.tenant_id]) for r in admitted]
            _, stalled = self._kv_gate(cands)
            if stalled:
                unbump = np.zeros(len(self._tenant_names), np.uint32)
                by_tenant: dict[int, list[Request]] = {}
                for i in stalled:
                    r, tidx = cands[i]
                    by_tenant.setdefault(tidx, []).append(r)
                for tidx, rs in by_tenant.items():
                    for r in reversed(rs):  # gate order = ticket order
                        self._tenant_queues[tidx].appendleft(r)
                        self._tenant_live[tidx] += 1
                        self.tenant_admitted[r.tenant_id] -= 1
                        unbump[tidx] += 1
                        r.fast = True
                self.qos = self.qos._replace(
                    consumed=self.qos.consumed - jnp.asarray(unbump))
                stall_ids = {id(cands[i][0]) for i in stalled}
                admitted = [r for r in admitted if id(r) not in stall_ids]
        self._fcfs_sort(admitted)
        return admitted

    def _replenish_qos(self, freed: int) -> None:
        """Slot(s) freed: reclaim credit stranded by tombstones, then
        distribute the pool to tenants with unmet live demand by stride
        scheduling (shares → weights under saturation); the replenish pokes
        the TWAHash buckets of the enabled ticket windows."""
        if self._use_kernel:
            # the fused kernel round replenishes in-graph each step — just
            # bank the freed slot(s) for the next round's pool
            self._qos_free += freed
            return
        depths = jnp.asarray(self._tenant_live, jnp.int32)
        self.qos, reclaimed = qos_reclaim(self.qos, depths)
        self._qos_free += freed + int(reclaimed)
        if self._qos_free > 0:
            self.qos, alloc, leftover = qos_replenish(
                self.qos, self._qos_free, depths, self.n_slots)
            self._qos_free = int(leftover)
            # Exact host-side wake on top of the bucket pokes: the engine
            # knows each replenished tenant's head, so flag it directly —
            # admission never depends on the conservative poke window alone.
            for tidx in np.flatnonzero(np.asarray(alloc)):
                for r in self._tenant_queues[tidx]:
                    if not r.expired:
                        r.fast = True
                        break

    # --------------------------------------------------------- scheduler ----

    def _admit_ready(self):
        """Admit backlog requests whose ticket < grant. TWA-style: only
        re-examine requests whose bucket moved since they last looked."""
        if self._tenants is not None:
            return self._admit_ready_qos()
        if not self.backlog:
            return []
        buckets = jnp.asarray([r.bucket for r in self.backlog], jnp.int32)
        observed = jnp.asarray([r.observed_seq for r in self.backlog], jnp.uint32)
        woken = np.asarray(woken_mask(self.sema, observed, buckets))
        admitted = []
        still = []
        grant = int(self.sema.grant)
        for r, w in zip(self.backlog, woken):
            if not (w or r.fast):
                # bucket untouched ⇒ grant can't have reached this ticket
                # (absent hash aliasing, which only causes extra checks);
                # `fast` rows were admitted at take time — the paper's
                # uncontended fast-path return.
                self.stats.backlog_skipped += 1
                still.append(r)
                continue
            self.stats.backlog_scans += 1
            r.observed_seq = int(self.sema.bucket_seq[r.bucket])
            if (grant - r.ticket) % (1 << 32) < (1 << 31) and r.ticket < grant:
                admitted.append(r)
            else:
                still.append(r)
        # FCFS safety: admission order == ticket order by construction
        admitted.sort(key=lambda r: r.ticket)
        self.backlog = still
        return admitted

    def _finish(self, slot: int, reason: str):
        """Retire a slot.  ``reason == "deadline"`` is decode preemption:
        the sequence is tombstoned (expired mid-decode), not completed —
        same slot-release path, different accounting."""
        req = self.active.pop(slot)
        req.finish_clock = self._now_r
        self.free_slots.append(slot)
        if reason == "deadline":
            req.expired = True
            req.preempted = True
            req.expire_round = self._round_no
            self.stats.preempted += 1
            self.stats.expired += 1
            if self._tenants is not None:
                self.tenant_expired[req.tenant_id] += 1
        else:
            self.stats.finished += 1
        if self._kv_pool is not None:
            if self._chunk:
                # incremental mode: the blocks the sequence ACTUALLY took
                # post back, and the host block semaphore pokes the
                # waiting-array buckets of the enabled range — exactly the
                # device `pool_release`, so parked requests observe the
                # same wake sequence the megastep path would.  In sharing
                # mode the caller already decref'd the slot's table row in
                # ONE batched `_hshare_release` (kv_blocks was zeroed) —
                # only the last sharer's release moves the counter.
                if req.kv_blocks:
                    self._kv_free_blocks += req.kv_blocks
                    self._kv_sema = post_batch(self._kv_sema,
                                               req.kv_blocks)
                req.kv_blocks = 0
                req.parked = False
            else:
                # the sequence's worst-case block reservation posts back —
                # the host counter mirrors the device block semaphore's
                # `post`, and the semaphore mirror pokes the waiting-array
                # buckets of the enabled range (sequential per-slot posts
                # bump the same buckets as the device's one batched
                # `pool_release` — poke ranges tile [grant, grant+Σ), and
                # bucket bumps commute)
                self._kv_free_blocks += self._kv_demand(req)
                self._kv_sema = post_batch(self._kv_sema,
                                           self._kv_demand(req))
        # slot freed → post: advances grant AND pokes the bucket of the next
        # waiting ticket (successor staging — the paper's SemaPost).  In QoS
        # mode the freed slot instead re-enters the weighted replenishment.
        if self._tenants is not None:
            self._replenish_qos(1)
        else:
            self.sema = post_batch(self.sema, 1)
            self._sema_grant_h += 1
        self.stats.wakeups += 1
        self._obs_done(req)
        req.done_event.set()
        self._client_sem.post()

    def _preempt_expired(self):
        """Deadline-aware decode preemption (host path, both modes): a
        RUNNING sequence whose deadline passed is tombstoned and its slot
        freed BEFORE this step's admission, so the reclaimed unit feeds the
        same round's replenish and the next live ticket is re-granted in
        FCFS order (the megastep does the identical thing in-graph)."""
        now = self._clock()
        # ascending slot order — the device preempt mask is walked lane-
        # ascending, and the trace events below must list in that order
        due = sorted(slot for slot, req in self.active.items()
                     if req.deadline is not None and req.deadline <= now)
        for slot in due:
            r = self.active[slot]
            self._ev_preempt.append(
                [EV_PREEMPT, r.rid, slot, len(r.out_tokens)])
        if self._kv_share:
            # the device preempt phase decrefs every preempted slot's row
            # in ONE batched pool_release — mirror it on the replica, then
            # let _finish retire the slots with nothing left to release
            self._hshare_release(due)
            for slot in due:
                self.active[slot].kv_blocks = 0
        for slot in due:
            self._finish(slot, "deadline")

    def step(self, sample_fn: Callable[[np.ndarray], np.ndarray]) -> int:
        """One engine iteration: preempt expired → admit → prefill admitted
        → decode active.  Returns number of active rows.  Under a profiler
        session its phases are timed (`repro.obs.trace.span`): ``sched.step``
        around ``sched.admit``, the prefills, the decode call,
        ``sched.nonfinite``, ``sched.sample``, ``sched.finish`` and
        ``sched.telemetry``."""
        with span("sched.step"):
            return self._step(sample_fn)

    def _step(self, sample_fn: Callable[[np.ndarray], np.ndarray]) -> int:
        if self._kv_pool is not None and self._kv_state is not None:
            # the device block pool already tracks reservations the host
            # counter can't see — a host admission here would double-book
            # blocks and decode against tables that don't exist on device
            raise RuntimeError(
                "paged engine is decoding via megastep; host step() would "
                "desync the device block pool (serve a kv_pool engine "
                "through ONE of the two paths)")
        with self._lock:
            rnd = self._round_no
            # ONE nominal host sync per step (the paired megastep counts 1
            # per K rounds); `telemetry()` and sample recording are pure
            # host-side reads and must never bump this
            self.stats.host_syncs += 1
            now_r = self._now_r = self._clock()
            self._round_gate_stalls = 0
            self._ev_preempt, self._ev_admit, self._ev_attach = [], [], []
            self._ev_park, self._ev_resume, self._ev_chunk = [], [], []
            self._ev_cow, self._ev_finish = [], []
            self._round_prefill_tokens = 0
            self._round_prefill_chunks = 0
            self._round_prefix_hits = 0
            self._round_cow_copies = 0
            self._round_nonfinite = self._nonfinite_sticky
            a0, e0, p0 = (self.stats.admitted, self.stats.expired,
                          self.stats.preempted)
            self._preempt_expired()
            with span("sched.admit"):
                admitted = self._admit_ready()
                if admitted:
                    # mirror the device `_assign_slots` in EVERY mode: admits
                    # in packed-FCFS-key order take ASCENDING free slots.
                    # Under sharing this is load-bearing for block identities
                    # (a slot's take pulls ids off the free queue in slot
                    # order); in the counter-only modes it pins the ADMIT
                    # trace events' slot column to the in-scan event table
                    # bit-exactly (tests/test_obs.py).
                    from .engine_state import _D_CLAMP, _T_BITS

                    if self._tenants is not None:
                        count("d2h")
                        grants = np.asarray(self.qos.grant)
                    else:
                        grants = None
                        g0 = int(self.sema.grant)
                    for r in admitted:
                        tidx = (self._tindex[r.tenant_id]
                                if grants is not None else 0)
                        d = (r.ticket
                             - (int(grants[tidx]) if grants is not None
                                else g0)) & 0xFFFFFFFF
                        d = d - (1 << 32) if d >= (1 << 31) else d
                        r.prio_key = (max(-_D_CLAMP, min(_D_CLAMP, d))
                                      << _T_BITS) + tidx
                    admitted = sorted(admitted, key=lambda r: r.prio_key)
                    self.free_slots.sort(reverse=True)
            for req in admitted:
                slot = self.free_slots.pop()
                req.slot = slot
                req.admit_t = time.time()
                req.admit_round = rnd
                req.last_adv_round = rnd  # assignment arms the watchdog
                self.active[slot] = req
                self.stats.admitted += 1
                self._ev_admit.append(
                    [EV_ADMIT, req.rid, slot,
                     min(len(req.prompt), self._prompt_cap) or 1])
                if self._chunk:
                    # chunked: no instant prefill — the chunk phase below
                    # streams the prompt in; prefill_fn fires on the round
                    # the last chunk lands (full KV available)
                    req.prefill_pos = 0
                    req.kv_blocks = 0
                    if self._kv_share and req.share is not None:
                        # attach the gate's cache hit: seed the shared
                        # block ids into the slot's table row and incref
                        # each (no counter moves, no pokes — the device
                        # round's phase 3a); the KV cursor resumes AT the
                        # divergence point, so the covered tokens cost
                        # zero prefill flops and zero new HBM
                        c_i, bids_i, tail_i, cov_i = req.share
                        req.share = None
                        ids = [int(b) for b in bids_i[:c_i]]
                        if tail_i >= 0:
                            ids.append(tail_i)
                        ids = ids[:self._kv_mb]
                        if ids:
                            self._kv_htbl[slot, :len(ids)] = ids
                            self._kv_hpool = pool_incref(
                                self._kv_hpool,
                                jnp.asarray(ids, jnp.int32),
                                jnp.ones(len(ids), bool))
                            self._hshare_sync()
                        req.prefill_pos = cov_i
                        req.kv_blocks = len(ids)
                        if cov_i > 0:
                            self._ev_attach.append(
                                [EV_PREFIX_ATTACH, req.rid, slot, cov_i])
                        pl = min(len(req.prompt), self._prompt_cap) or 1
                        if cov_i >= pl:  # fully covered: decode-ready now
                            self._round_prefix_hits += 1
                else:
                    self.prefill_fn(req)  # engine-owner fills the row's cache

            if not self.active:
                self._round_no = rnd + 1
                with span("sched.telemetry"):
                    self._record_round(self._host_sample(rnd, now_r, a0, e0,
                                                         p0, 0))
                return 0
            self.stats.steps += 1
            if self._chunk:
                decode = [(int(s), self.active[int(s)])
                          for s in self._chunk_step()]
            else:
                decode = list(self.active.items())
            if decode:
                logits = self.step_fn([r for _, r in decode])
                with span("sched.nonfinite"):
                    if (isinstance(logits, np.ndarray)
                            and logits.dtype.kind == "f"
                            and not np.all(np.isfinite(logits))):
                        self._round_nonfinite = True  # H_NAN sentinel input
                with span("sched.sample"):
                    next_tokens = sample_fn(logits)
                with span("sched.finish"):
                    done_slots = []
                    for (slot, req), tok in zip(decode, next_tokens):
                        req.out_tokens.append(int(tok))
                        req.last_adv_round = rnd  # progress re-arms watchdog
                        if req.first_tok_clock is None:
                            req.first_tok_clock = now_r
                        req.last_tok_clock = now_r
                        if len(req.out_tokens) >= req.max_new_tokens:
                            done_slots.append(slot)
                    for slot in sorted(done_slots):  # device fin mask: lane-
                        r = self.active[slot]        # ascending event order
                        self._ev_finish.append(
                            [EV_FINISH, r.rid, slot, len(r.out_tokens)])
                    if self._kv_share:
                        # ONE batched decref for the whole finish phase (the
                        # device round's completion release) before the
                        # per-slot retirement bookkeeping
                        self._hshare_release(done_slots)
                        for slot in done_slots:
                            self.active[slot].kv_blocks = 0
                    for slot in done_slots:
                        self._finish(slot, "length")
            self.stats.prefix_hits += self._round_prefix_hits
            self.stats.cow_copies += self._round_cow_copies
            self._round_no = rnd + 1
            with span("sched.telemetry"):
                self._record_round(self._host_sample(rnd, now_r, a0, e0, p0,
                                                     len(decode)))
            return len(self.active)

    def _chunk_step(self) -> np.ndarray:
        """Host chunk phase — ONE call into the SAME jitted planner the
        scanned megastep uses (`serving.prefill.chunk_plan` over
        `banker_order`), applied to the per-request host state: split the
        prefill token budget, take blocks incrementally from the host
        block-semaphore mirror, park the block-stalled requests on its
        waiting array, and return the decode-ready slot indices.  Because
        planner, order, and semaphore arithmetic are shared with
        `engine_state._chunk_phase`, host-loop and megastep serving stay
        bit-identical round-for-round (tests/test_chunked_prefill.py)."""
        from ..core.functional import park_state
        from .prefill import banker_order, chunk_plan

        S = self.n_slots
        sharing = self._kv_share
        busy = np.zeros(S, bool)
        parked = np.zeros(S, bool)
        woken = np.zeros(S, bool)
        pos = np.zeros(S, np.int32)
        plen = np.zeros(S, np.int32)
        mxn = np.zeros(S, np.int32)
        held = np.zeros(S, np.int32)
        prio_r = np.zeros(S, np.int32)
        prio_k = np.zeros(S, np.int32)
        seq = np.asarray(self._kv_sema.bucket_seq)
        rem = np.zeros(S, np.int32)
        rids = np.full(S, -1, np.int32)
        for s, r in self.active.items():
            pl = min(len(r.prompt), self._prompt_cap) or 1
            rids[s] = r.rid
            busy[s] = True
            parked[s] = r.parked
            woken[s] = r.parked and seq[r.park_bucket] != r.park_seq
            pos[s] = (r.prefill_pos if r.prefill_pos < pl
                      else pl + len(r.out_tokens))
            plen[s] = pl
            mxn[s] = r.max_new_tokens
            held[s] = r.kv_blocks
            rem[s] = self._kv_rem(r)
            prio_r[s] = r.admit_round
            prio_k[s] = r.prio_key
        if sharing:
            cow_a, held_free = self._kv_share_state()
            rem = rem + cow_a.astype(np.int32)  # a pending COW owes 1 more
        else:
            cow_a, held_free = np.zeros(S, bool), held
        order = banker_order(rem, prio_r, prio_k, busy)
        plan = chunk_plan(order, busy, parked, woken, pos, plen, mxn, held,
                          self._kv_free_blocks, cow_a, held_free,
                          chunk=self._chunk, budget=self._budget,
                          block_size=self._kv_bs)
        take = np.asarray(plan.take)
        tokens = np.asarray(plan.tokens)
        parked_o = np.asarray(plan.parked)
        deficit = np.asarray(plan.deficit)
        newly = parked_o & (deficit > 0)
        # trace events — PARK/RESUME on park-state TRANSITIONS, one
        # PREFILL_CHUNK per slot that landed tokens; lane-ascending, the
        # same masks/args the device `_chunk_phase` folds into the table
        for s in np.flatnonzero(parked_o & ~parked):
            self._ev_park.append([EV_PARK, int(rids[s]), int(s),
                                  int(deficit[s])])
        for s in np.flatnonzero(parked & ~parked_o):
            self._ev_resume.append([EV_RESUME, int(rids[s]), int(s), 0])
        for s in np.flatnonzero(tokens > 0):
            self._ev_chunk.append([EV_PREFILL_CHUNK, int(rids[s]), int(s),
                                   int(tokens[s])])
        if sharing:
            # the replica takes the granted blocks through the SAME
            # `pool_try_alloc` the scanned round uses (free-queue cursor,
            # park registration), scatters the fresh ids into the table —
            # a COW grant REPLACES the shared tail at column held−1, whose
            # old id is decref'd in ONE batched release — and resyncs the
            # counter mirrors off the mutated pool
            cow_g = np.asarray(plan.cow)
            max_take = -(-self._chunk // self._kv_bs) + 1
            hp, ids, bkt_j, sq_j = pool_try_alloc(
                self._kv_hpool, plan.take, max_take,
                park=jnp.asarray(newly), deficit=plan.deficit)
            ids = np.asarray(ids)
            bkt, sq = np.asarray(bkt_j), np.asarray(sq_j)
            old = self._kv_htbl[np.arange(S),
                                np.clip(held - 1, 0, self._kv_mb - 1)]
            for s in np.flatnonzero(cow_g):  # arg = the replaced block id
                self._ev_cow.append([EV_COW, int(rids[s]), int(s),
                                     int(old[s])])
            base = np.where(cow_g, held - 1, held)
            for s in range(S):
                for k in range(int(take[s])):
                    if 0 <= base[s] + k < self._kv_mb:
                        self._kv_htbl[s, base[s] + k] = ids[s, k]
            if cow_g.any():
                hp = pool_release(hp, jnp.asarray(old),
                                  jnp.asarray(cow_g))
            self._kv_hpool = hp
            self._hshare_sync()
            self._round_cow_copies = int(cow_g.sum())
        else:
            if newly.any():
                bkt, sq = park_state(self._kv_sema,
                                     np.maximum(deficit, 1)
                                     .astype(np.uint32))
                bkt, sq = np.asarray(bkt), np.asarray(sq)
            total = int(take.sum())
            self._kv_free_blocks -= total
            self._kv_sema = self._kv_sema._replace(
                ticket=self._kv_sema.ticket + jnp.uint32(total))
        for s, r in self.active.items():
            pl = int(plen[s])
            if sharing:
                r.kv_blocks = int((self._kv_htbl[s] >= 0).sum())
            else:
                r.kv_blocks += int(take[s])
            r.parked = bool(parked_o[s])
            if newly[s]:
                r.park_bucket = int(bkt[s])
                r.park_seq = int(sq[s])
            if tokens[s]:
                r.prefill_pos += int(tokens[s])
                r.last_adv_round = self._round_no  # chunk landed: progress
                if r.prefill_pos >= pl:
                    self.prefill_fn(r)  # last chunk landed: full KV ready
        if sharing:
            # publish prefixes at prefill COMPLETION — the device round's
            # phase 4b, against the post-take post-COW pool/table (no pool
            # op intervenes between here and there on either path)
            comp = busy & (pos < plen) & (pos + tokens >= plen)
            if comp.any():
                sph = np.zeros((S, 2, self._hash_w + 1), np.uint32)
                for s, r in self.active.items():
                    sph[s] = np.asarray(r.ph, np.uint32)
                self._kv_cache = cache_register(
                    self._kv_cache, self._kv_hpool, jnp.asarray(sph),
                    jnp.asarray(plen), jnp.asarray(self._kv_htbl),
                    jnp.asarray(comp), self._kv_bs)
        self.stats.prefill_chunks += int((tokens > 0).sum())
        self.stats.kv_block_stalls += int(parked_o.sum())
        self._round_prefill_tokens = int(tokens.sum())
        self._round_prefill_chunks = int((tokens > 0).sum())
        return np.flatnonzero(np.asarray(plan.emit))

    # ----------------------------------------------------------- megastep ---

    def megastep(self, K: int, *, token_fn=None, admit_fn=None,
                 nows=None, admit_impl="auto") -> int:
        """Device-resident decode megastep: K fused engine rounds as ONE
        jitted `lax.scan` (`serving.engine_state.megastep_jit`) over a
        donated on-device :class:`~repro.serving.engine_state.EngineState`
        — the host syncs once per K decoded tokens (launch + one drain of
        the (K, S) token/event buffers) instead of once per token.

        Each scanned round fuses: deadline preemption of running slots →
        the QoS admission round (preemption-freed units feed the SAME
        round's replenish) → FCFS slot assignment through the free-slot
        TWA semaphore → ``token_fn`` decode+sample → completion
        retirement.  Round-for-round identical to K sequential `step()`
        calls (property-tested in tests/test_megastep.py).

        ``token_fn(model, EngineState) -> (tokens (S,) i32, model')`` and
        the optional in-graph prefill hook ``admit_fn(model, state, rows,
        mask, slots) -> model'`` must be jittable; the model pytree lives
        in ``self.megastep_model`` and is donated across launches.
        ``nows``: optional (K,) float timestamps RELATIVE to launch
        (default: all 0.0 — time frozen at launch for the whole
        megastep).  ``admit_impl`` overrides the in-graph admission-round
        implementation (``"auto"``: the fused Pallas pass on TPU when
        ``use_kernel``, else the functional path; tests pass
        `engine_state.fused_round_impl` explicitly to exercise the kernel
        in interpret mode — bit-identical either way).

        With ``kv_pool=`` the scanned round allocates from / releases to
        the block-paged KV pool; the device `KVPool` (block semaphore +
        tables) persists across launches alongside ``megastep_model``, so
        paged engines must decode through megastep (host `step()` keeps
        only the free-block counter).  With ``chunked_prefill=`` every
        scanned round additionally co-schedules prompt chunks with decode
        (incremental block takes, waiting-array parks — see
        `serving.engine_state`); ``token_fn`` must handle the prefill
        phase (`engine_state.chunked_prefill_token_fn` or the
        static-window factory), and per-request prefill/park state rides
        host↔device across launches.  Returns the number of busy slots
        after the last round.
        """
        from .engine_state import (
            KVPool,
            Slots,
            fused_round_impl,
            make_engine_state,
            megastep_jit,
            zero_token_fn,
        )

        if self._tenants is None:
            raise ValueError("megastep requires QoS mode (tenants=...)")
        if K < 1:
            raise ValueError("megastep needs K >= 1")
        token_fn = token_fn or zero_token_fn
        window = getattr(token_fn, "_chunk_window", None)
        if self._chunk and window is not None and window < self._chunk:
            # a narrower scatter window than the engine's chunk would
            # silently drop the tail of every scheduled chunk (pos still
            # advances by the full chunk) — corrupt KV, no error
            raise ValueError(
                f"token_fn chunk window ({window}) is smaller than the "
                f"engine's chunk size ({self._chunk}); build it with "
                f"make_chunked_prefill_token_fn({self._chunk})")
        with self._lock:
            self.stats.host_syncs += 1
            base = self._round_no
            t0 = self._clock()
            S = self.n_slots

            # Round-robin drain of the tenant queues up to the device
            # backlog capacity: truncation at the cap only ever cuts
            # per-tenant queue TAILS, so FCFS within a tenant is preserved
            # (dropped rows simply wait for a later megastep).
            qs = [[r for r in q if not r.expired]
                  for q in self._tenant_queues]
            heads = [0] * len(qs)
            rows: list[Request] = []
            while len(rows) < self._backlog_cap:
                moved = False
                for qi, q in enumerate(qs):
                    if heads[qi] < len(q) and len(rows) < self._backlog_cap:
                        rows.append(q[heads[qi]])
                        heads[qi] += 1
                        moved = True
                if not moved:
                    break
            n = len(rows)
            # power-of-two shape buckets: steady-state serving re-uses one
            # compiled executable per (B, P, K) bucket instead of
            # retracing per backlog length (cf. kernels.ops._pad_backlog)
            B = max(_next_pow2(max(n, S)), 8)
            maxp = max([len(r.prompt) for r in rows]
                       + [len(r.prompt) for r in self.active.values()] + [1])
            P = min(_next_pow2(maxp), self._prompt_cap)

            paged = self._kv_pool is not None
            if paged and self._kv_state is None and self.active:
                # slots admitted by host step() have no device block
                # tables — their KV does not exist in the pool
                raise RuntimeError(
                    "paged engine has host-admitted active slots; serve a "
                    "kv_pool engine exclusively via megastep")
            fresh_kv = paged and self._kv_state is None
            sharing = self._kv_share
            state = make_engine_state(
                self.qos, S, B, P, free_units=self._qos_free,
                kv_blocks=self._kv_blocks if fresh_kv and not sharing else 0,
                kv_slot_blocks=self._kv_mb if fresh_kv and not sharing
                else 0,
                # in-scan telemetry ring: pow2 ≥ K so one launch never
                # wraps (pow2 also buckets the compile cache with K)
                ring_cap=_next_pow2(K))
            if paged and not fresh_kv:
                # block semaphore + tables persist launch-to-launch (the
                # pool's identity mapping must survive with the model KV);
                # building a throwaway fresh pool first would waste an
                # (S, MB) table + NB-entry queue allocation per launch
                state = state._replace(kv=self._kv_state)
            elif paged and sharing:
                # first launch under sharing ADOPTS the host replica —
                # pool generations and cache entries accumulated by prior
                # host step() rounds stay authoritative, and the carried
                # pool below replaces the replica after the scan
                state = state._replace(kv=KVPool(
                    pool=self._kv_hpool, tbl=jnp.asarray(self._kv_htbl),
                    cache=self._kv_cache))
            valid = np.zeros(B, bool)
            ids = np.zeros(B, np.int32)
            tks = np.zeros(B, np.uint32)
            dls = np.full(B, np.inf, np.float32)
            rid = np.full(B, -1, np.int32)
            mx = np.zeros(B, np.int32)
            pl = np.zeros(B, np.int32)
            pr = np.zeros((B, P), np.int32)
            if sharing:
                bph = np.zeros((B, 2, self._hash_w + 1), np.uint32)
                sph = np.zeros((S, 2, self._hash_w + 1), np.uint32)
            for i, r in enumerate(rows):
                valid[i] = True
                ids[i] = self._tindex[r.tenant_id]
                tks[i] = r.ticket
                if r.deadline is not None:
                    dls[i] = r.deadline - t0
                rid[i] = r.rid
                mx[i] = r.max_new_tokens
                p = r.prompt[-P:] if r.prompt else [0]
                pl[i] = len(p)
                pr[i, :len(p)] = p
                if sharing:
                    bph[i] = np.asarray(r.ph, np.uint32)
            sb = np.zeros(S, bool)
            srow = np.full(S, -1, np.int32)
            srid = np.full(S, -1, np.int32)
            sten = np.zeros(S, np.int32)
            sdl = np.full(S, np.inf, np.float32)
            smx = np.zeros(S, np.int32)
            sem = np.zeros(S, np.int32)
            stok = np.zeros(S, np.int32)
            spos = np.zeros(S, np.int32)
            spl = np.zeros(S, np.int32)
            sprm = np.zeros((S, P), np.int32)
            spri_r = np.zeros(S, np.int32)
            spri_k = np.zeros(S, np.int32)
            sprk = np.zeros(S, bool)
            spb = np.zeros(S, np.int32)
            sps = np.zeros(S, np.uint32)
            sladv = np.zeros(S, np.int32)
            chunked = self._chunk > 0
            for slot, r in self.active.items():
                sb[slot] = True
                # watchdog clock rides host↔device with the slot (the
                # stuck-slot sentinel counts from the last progress round)
                sladv[slot] = r.last_adv_round
                srow[slot] = B + slot  # host-resolved: active at launch
                srid[slot] = r.rid
                sten[slot] = self._tindex[r.tenant_id]
                if r.deadline is not None:
                    sdl[slot] = r.deadline - t0
                smx[slot] = r.max_new_tokens
                sem[slot] = len(r.out_tokens)
                stok[slot] = (r.out_tokens[-1] if r.out_tokens
                              else (r.prompt[-1] if r.prompt else 0))
                # device position, NOT raw prompt length: prompts longer
                # than the cap were truncated at admission, and the paged
                # block tables / dense ring cursors index by the DEVICE
                # cursor — an untruncated re-seed would shift every later
                # KV write past the reservation
                plen_t = min(len(r.prompt), self._prompt_cap) or 1
                spl[slot] = plen_t
                if chunked:
                    # mid-prefill slots resume at their chunk cursor; the
                    # remaining prompt must ride along (the backlog row
                    # that held it was recycled at admission)
                    spos[slot] = (r.prefill_pos if r.prefill_pos < plen_t
                                  else plen_t + len(r.out_tokens))
                    p = r.prompt[-P:] if r.prompt else [0]
                    sprm[slot, :len(p)] = p
                    spri_r[slot] = r.admit_round
                    spri_k[slot] = r.prio_key
                    sprk[slot] = r.parked
                    spb[slot] = r.park_bucket
                    sps[slot] = r.park_seq
                    if sharing:
                        sph[slot] = np.asarray(r.ph, np.uint32)
                else:
                    spos[slot] = plen_t + len(r.out_tokens)
            state = state._replace(
                round_no=jnp.asarray(base, jnp.int32),
                stalls=jnp.asarray(self.stats.kv_block_stalls, jnp.int32),
                chunks=jnp.asarray(self.stats.prefill_chunks, jnp.int32),
                backlog=state.backlog._replace(
                    valid=jnp.asarray(valid), tenant=jnp.asarray(ids),
                    ticket=jnp.asarray(tks), deadline=jnp.asarray(dls),
                    rid=jnp.asarray(rid), max_new=jnp.asarray(mx),
                    prompt=jnp.asarray(pr), prompt_len=jnp.asarray(pl),
                    **({"ph": jnp.asarray(bph)} if sharing else {})),
                slots=Slots(
                    busy=jnp.asarray(sb), row=jnp.asarray(srow),
                    rid=jnp.asarray(srid), tenant=jnp.asarray(sten),
                    deadline=jnp.asarray(sdl), max_new=jnp.asarray(smx),
                    emitted=jnp.asarray(sem), token=jnp.asarray(stok),
                    pos=jnp.asarray(spos), plen=jnp.asarray(spl),
                    prompt=jnp.asarray(sprm), prio_r=jnp.asarray(spri_r),
                    prio_k=jnp.asarray(spri_k), parked=jnp.asarray(sprk),
                    park_bucket=jnp.asarray(spb), park_seq=jnp.asarray(sps),
                    chunk=jnp.zeros(S, jnp.int32),
                    last_adv=jnp.asarray(sladv),
                    **({"ph": jnp.asarray(sph),
                        "cow_src": jnp.full((S,), -1, jnp.int32)}
                       if sharing else {})),
                slot_sema=state.slot_sema._replace(
                    ticket=jnp.uint32(int(sb.sum()))))

            if nows is None:
                nows_a = np.zeros(K, np.float32)
            else:
                nows_a = np.asarray(nows, np.float32)
                if nows_a.shape != (K,):
                    raise ValueError(f"nows must be shape ({K},)")
            if admit_impl == "auto":
                from ..kernels.ops import use_interpret

                admit_impl = (fused_round_impl
                              if self._use_kernel and not use_interpret()
                              else None)

            # donation requires every leaf to own a distinct buffer: the
            # freshly-built state is small (copy unconditionally — fresh
            # QoS states alias one zeros buffer across fields); the model
            # (KV caches — the big pytree) is copied only on first
            # adoption, then flows donated launch-to-launch.
            state = jax.tree_util.tree_map(
                lambda x: jnp.array(x, copy=True), state)
            model = self.megastep_model if self.megastep_model is not None \
                else ()
            if model is not getattr(self, "_megastep_model_last", None):
                model = jax.tree_util.tree_map(
                    lambda x: jnp.array(x, copy=True), model)
            with span("sched.megastep"):
                st, model, ys = megastep_jit(
                    state, model, jnp.asarray(nows_a), token_fn=token_fn,
                    admit_fn=admit_fn, admit_impl=admit_impl,
                    block_size=self._kv_bs if paged else 0,
                    chunk=self._chunk if paged else 0,
                    budget=self._budget if paged else 0,
                    commit=self._kv_commit if paged else 0,
                    watchdog=self._watchdog)
                self.megastep_model = model
                self._megastep_model_last = model

                # ---- the ONE host sync: drain state + event buffers ----
                st_h, ys_h = jax.device_get((st, ys))
            prev_active = dict(self.active)

            def req_of(row: int) -> Request:
                return rows[row] if row < B else prev_active[row - B]

            gone = set()
            for i, r in enumerate(rows):
                tidx = self._tindex[r.tenant_id]
                if st_h.backlog.admit_round[i] >= 0:
                    r.admit_round = int(st_h.backlog.admit_round[i])
                    r.admit_t = time.time()
                    r.slot = int(st_h.backlog.slot[i])
                    self.stats.admitted += 1
                    self.tenant_admitted[r.tenant_id] += 1
                    self._tenant_live[tidx] -= 1
                    gone.add(id(r))
                elif st_h.backlog.expire_round[i] >= 0:
                    # stamp the tombstone's round clock BEFORE _expire_req
                    # so its obs event carries the in-scan expiry time, not
                    # the drain-time clock
                    r.expire_round = int(st_h.backlog.expire_round[i])
                    r.finish_clock = t0 + float(
                        nows_a[r.expire_round - base])
                    self._expire_req(r, tidx)
                    r.expire_round = int(st_h.backlog.expire_round[i])
                    self._tenant_live[tidx] -= 1
                    gone.add(id(r))
            if gone:
                for tidx, q in enumerate(self._tenant_queues):
                    self._tenant_queues[tidx] = deque(
                        r for r in q if id(r) not in gone)

            for k in range(K):
                tk = t0 + float(nows_a[k])  # round k's clock (absolute)
                for s in np.flatnonzero(ys_h.pre[k]):
                    r = req_of(int(ys_h.prerow[k][s]))
                    r.expired = True
                    r.preempted = True
                    r.expire_round = base + k
                    r.finish_clock = tk
                    self.stats.preempted += 1
                    self.stats.expired += 1
                    self.tenant_expired[r.tenant_id] += 1
                    self.stats.wakeups += 1
                    self._obs_done(r)
                    r.done_event.set()
                    self._client_sem.post()
                for s in np.flatnonzero(ys_h.emit[k]):
                    r = req_of(int(ys_h.row[k][s]))
                    r.out_tokens.append(int(ys_h.tokens[k][s]))
                    if r.first_tok_clock is None:
                        r.first_tok_clock = tk
                    r.last_tok_clock = tk
                for s in np.flatnonzero(ys_h.fin[k]):
                    r = req_of(int(ys_h.row[k][s]))
                    r.finish_clock = tk
                    self.stats.finished += 1
                    self.stats.wakeups += 1
                    self._obs_done(r)
                    r.done_event.set()
                    self._client_sem.post()
            self.stats.steps += int((ys_h.n_active > 0).sum())
            self.stats.backlog_scans += int(ys_h.n_live.sum())

            # drop resolved entries from the host expiry heap (only the
            # non-kernel step() path pops it — a megastep-only engine
            # would otherwise retain every deadline Request forever)
            if self._deadline_heap:
                self._deadline_heap = [
                    e for e in self._deadline_heap
                    if not (e[2].expired or e[2].slot is not None
                            or e[2].done_event.is_set())]
                heapq.heapify(self._deadline_heap)

            self.active = {int(s): req_of(int(st_h.slots.row[s]))
                           for s in np.flatnonzero(st_h.slots.busy)}
            self.free_slots = [s for s in range(S)
                               if not st_h.slots.busy[s]]
            for s, r in self.active.items():
                # the watchdog clock rides back to the host mirror so the
                # next launch (or a host step) resumes the same count
                r.last_adv_round = int(st_h.slots.last_adv[s])
            self._qos_free = int(st_h.free)
            self.qos = st.qos  # keep the (fresh) device arrays
            if paged:
                self._kv_state = st.kv
                # host counter ← the block semaphore's counter identity
                self._kv_free_blocks = int(np.int32(
                    np.uint32(st_h.kv.pool.sema.grant)
                    - np.uint32(st_h.kv.pool.sema.ticket)))
                # the host block-semaphore mirror resyncs to the device
                # counters/buckets in BOTH paged modes (it feeds the
                # kv_pokes telemetry probe) — mixed step()/megastep
                # serving raises above, but the mirror must never be
                # allowed to go stale against carried park state
                self._kv_sema = st.kv.pool.sema
                if sharing:
                    # replica ← post-scan device pool/table/cache (one
                    # object: the next host step() or launch continues
                    # from the scanned state); refcnt mirror off the SAME
                    # device_get so telemetry() stays sync-free
                    self._kv_hpool = st.kv.pool
                    self._kv_cache = st.kv.cache
                    self._kv_htbl = np.asarray(st_h.kv.tbl)
                    self._kv_refcnt_h = np.asarray(st_h.kv.pool.refcnt)
            if chunked:
                # carry each still-running request's prefill/park state to
                # the next launch (the device pool itself persists in
                # _kv_state; this is the per-request view of it)
                tbl_h = np.asarray(st_h.kv.tbl)
                for s, r in self.active.items():
                    r.prefill_pos = int(st_h.slots.pos[s])
                    r.prio_key = int(st_h.slots.prio_k[s])
                    r.parked = bool(st_h.slots.parked[s])
                    r.park_bucket = int(st_h.slots.park_bucket[s])
                    r.park_seq = int(st_h.slots.park_seq[s])
                    r.kv_blocks = int((tbl_h[s] >= 0).sum())
                self.stats.kv_block_stalls = int(st_h.stalls)
                self.stats.prefill_chunks = int(st_h.chunks)
            # drain the in-scan telemetry ring — part of the SAME device_get
            # above, so observability adds no host sync (host_syncs stays 1
            # per megastep; tests/test_obs.py pins this)
            from .engine_state import ring_samples

            self._last_samples = ring_samples(st_h.ring, t0=t0)
            for smp in self._last_samples:
                self._trace.ingest_sample(smp)
            if sharing:
                self.stats.prefix_hits += sum(
                    s["prefix_hits"] for s in self._last_samples)
                self.stats.cow_copies += sum(
                    s["cow_copies"] for s in self._last_samples)
            if self._obs is not None:
                for smp in self._last_samples:
                    self._obs.record_round(smp)
            self._round_no = base + K
            return int(st_h.slots.busy.sum())

    # ----------------------------------------------------------- recovery ---
    # The scheduler-owned rungs of the recovery ladder
    # (repro.resilience.recovery drives escalation policy; these methods
    # implement containment so EVERY serving mode — host loop, megastep,
    # paged, chunked — repairs through one audited path).

    def quarantine(self, slot: int) -> Request:
        """Rung 1 — evict a sick slot: release every block it holds (host
        mirror AND the persistent device pool, poking the waiting-array
        buckets exactly like a completion), return its slot unit to the
        replenishment pool, and hand the request back with its decode
        progress reset so the caller can re-submit it after a backoff
        (`Request.retries` carries the per-request budget).  The request's
        ``done_event`` is NOT set — it is still in flight."""
        from ..core.functional import pool_release

        with self._lock:
            req = self.active.pop(slot)
            self.free_slots.append(slot)
            self.stats.quarantined += 1
            # quarantine is a host-side recovery action BETWEEN rounds on
            # both serving paths — traced directly, never via the in-scan
            # table (arg = blocks the eviction hands back)
            self._trace.add(EV_QUARANTINE, req.rid, slot, req.kv_blocks,
                            self._clock(), self._round_no)
            if self._kv_pool is not None:
                if self._kv_state is not None:
                    # megastep-persistent pool: the device block table is
                    # ground truth — release ITS row (counter + free-queue
                    # + bucket pokes), clear it, and resync the host
                    # mirrors off the released pool's counter identity
                    kv = self._kv_state
                    onehot = jnp.arange(kv.tbl.shape[0]) == slot
                    pool = pool_release(kv.pool, kv.tbl, onehot)
                    self._kv_state = kv._replace(
                        pool=pool,
                        tbl=jnp.where(onehot[:, None], -1, kv.tbl))
                    self._kv_sema = pool.sema
                    self._kv_free_blocks = int(np.int32(
                        np.uint32(pool.sema.grant)
                        - np.uint32(pool.sema.ticket)))
                    if self._kv_share:
                        # replica follows the repaired device pool (the
                        # release above decref'd shared references — only
                        # last-sharer blocks actually re-entered the queue)
                        self._kv_hpool = pool
                        self._kv_htbl = np.asarray(self._kv_state.tbl)
                        self._kv_refcnt_h = np.asarray(pool.refcnt)
                elif self._kv_share:
                    # host-loop sharing: ONE batched decref-release of the
                    # slot's table row on the replica (frees + pokes only
                    # blocks whose last sharer this slot was)
                    self._hshare_release([slot])
                elif self._chunk:
                    self._kv_free_blocks += req.kv_blocks
                    self._kv_sema = post_batch(self._kv_sema, req.kv_blocks)
                else:
                    dem = self._kv_demand(req)
                    self._kv_free_blocks += dem
                    self._kv_sema = post_batch(self._kv_sema, dem)
            # reset decode progress: a requeued request replays from its
            # prompt (fresh ticket, fresh slot, fresh KV) — partial output
            # from the sick slot is untrusted by definition
            req.slot = None
            req.out_tokens.clear()
            req.prefill_pos = 0
            req.kv_blocks = 0
            req.parked = False
            req.admit_round = -1
            req.last_adv_round = -1
            req.first_tok_clock = None
            req.last_tok_clock = None
            req.fast = False
            # the freed unit re-enters admission like any completion
            if self._tenants is not None:
                self._replenish_qos(1)
            else:
                self.sema = post_batch(self.sema, 1)
                self._sema_grant_h += 1
            return req

    def audit_kv(self) -> dict:
        """Rung 2 — audit-and-rebuild the block pool from block-table
        ground truth.  The live tables are the only state a corrupted
        counter cannot forge (each busy slot's KV physically occupies its
        blocks): every id NOT owned by exactly one table cell is returned
        to the free queue, aliased duplicates are cleared from their later
        owners (reported as ``victims`` for the caller to quarantine), and
        the block semaphore's ticket is rewritten so ``grant − ticket``
        equals the true free count — ``grant`` itself is preserved, so the
        poke history parked slots observed stays valid.  All parked flags
        are cleared (stalled slots re-park against the repaired pool on
        their next round).  Returns a repair report."""
        if self._kv_pool is None:
            raise RuntimeError("audit_kv needs a block-paged pool "
                               "(kv_pool=...)")
        with self._lock:
            self.stats.kv_audits += 1
            NB = self._kv_blocks
            report = {"aliased": 0, "leaked": 0, "counter_drift": 0,
                      "victims": []}
            if self._kv_share:
                # refcounted rebuild: table REFERENCES are the ground
                # truth — refcnt := per-block reference count, free =
                # {refs == 0}, ticket = grant − free (grant preserved so
                # the poke history parked slots observed stays valid).
                # The weak prefix cache is dropped wholesale: no gen
                # stamp can be trusted about rebuilt identities
                # (`prefix.cache_clear`); future prefills re-register.
                # Note "aliased" loses its one-owner meaning here — a
                # block in two tables is a legitimate shared prefix —
                # so only out-of-range ids evict their cell.
                kv_dev = self._kv_state
                tbl = np.asarray(kv_dev.tbl if kv_dev is not None
                                 else self._kv_htbl).copy()
                pool = kv_dev.pool if kv_dev is not None else self._kv_hpool
                Sn, MB = tbl.shape
                refs = np.zeros(NB, np.int64)
                for s in range(Sn):
                    for j in range(MB):
                        b = tbl[s, j]
                        if b < 0:
                            continue
                        if b >= NB:
                            tbl[s, j] = -1
                            report["aliased"] += 1
                            if s not in report["victims"]:
                                report["victims"].append(s)
                        else:
                            refs[b] += 1
                free_ids = np.flatnonzero(refs == 0).astype(np.int32)
                n_free = len(free_ids)
                sema = pool.sema
                drift = n_free - int(np.int32(np.uint32(sema.grant)
                                              - np.uint32(sema.ticket)))
                report["counter_drift"] = int(drift)
                report["leaked"] = max(0, int(drift))
                report["refcnt_drift"] = int(
                    np.abs(refs - np.asarray(pool.refcnt)).sum())
                new_ticket = np.uint32(int(np.uint32(sema.grant)) - n_free)
                q = np.asarray(pool.free_q).copy()
                pos = (int(new_ticket) + np.arange(n_free)) & (NB - 1)
                q[pos] = free_ids
                new_pool = pool._replace(
                    sema=sema._replace(ticket=jnp.uint32(new_ticket)),
                    free_q=jnp.asarray(q),
                    refcnt=jnp.asarray(refs, jnp.int32))
                self._kv_cache = cache_clear(self._kv_cache)
                self._kv_hpool = new_pool
                self._kv_htbl = tbl
                if kv_dev is not None:
                    self._kv_state = kv_dev._replace(
                        pool=new_pool, tbl=jnp.asarray(tbl),
                        cache=self._kv_cache)
                self._hshare_sync()
                for s, r in self.active.items():
                    r.kv_blocks = int((tbl[s] >= 0).sum())
            elif self._kv_state is not None:
                kv = self._kv_state
                tbl = np.asarray(kv.tbl).copy()
                S, MB = tbl.shape
                owner = np.full(NB, -1, np.int64)
                for s in range(S):
                    for j in range(MB):
                        b = tbl[s, j]
                        if b < 0:
                            continue
                        if b >= NB or owner[b] >= 0:
                            # out-of-range or aliased: the LATER owner
                            # loses the cell (its KV is untrusted)
                            tbl[s, j] = -1
                            report["aliased"] += 1
                            if s not in report["victims"]:
                                report["victims"].append(s)
                        else:
                            owner[b] = s
                free_ids = np.flatnonzero(owner < 0).astype(np.int32)
                n_free = len(free_ids)
                sema = kv.pool.sema
                drift = n_free - int(np.int32(np.uint32(sema.grant)
                                              - np.uint32(sema.ticket)))
                report["counter_drift"] = int(drift)
                report["leaked"] = max(0, int(drift))
                # rebuild: free region occupies queue positions
                # [ticket, grant) — keep grant, set ticket = grant − free
                new_ticket = np.uint32(int(np.uint32(sema.grant)) - n_free)
                q = np.asarray(kv.pool.free_q).copy()
                pos = (int(new_ticket) + np.arange(n_free)) & (NB - 1)
                q[pos] = free_ids
                self._kv_state = kv._replace(
                    pool=kv.pool._replace(
                        sema=sema._replace(ticket=jnp.uint32(new_ticket)),
                        free_q=jnp.asarray(q)),
                    tbl=jnp.asarray(tbl))
                self._kv_sema = self._kv_state.pool.sema
                self._kv_free_blocks = n_free
                # host per-request held-block mirrors follow the table
                for s, r in self.active.items():
                    r.kv_blocks = int((tbl[s] >= 0).sum())
            else:
                # host-loop mode: the per-request counters are the ground
                # truth; reconcile the free counter and semaphore ticket
                if self._chunk:
                    held = sum(r.kv_blocks for r in self.active.values())
                else:
                    held = sum(self._kv_demand(r)
                               for r in self.active.values())
                n_free = NB - held
                drift = n_free - self._kv_free_blocks
                report["counter_drift"] = int(drift)
                report["leaked"] = max(0, int(drift))
                self._kv_free_blocks = n_free
                self._kv_sema = self._kv_sema._replace(
                    ticket=self._kv_sema.grant - jnp.uint32(n_free))
            for r in self.active.values():
                r.parked = False  # re-park (if still short) post-repair
            return report

    # ---------------------------------------------------------- telemetry ---

    def _obs_done(self, r: Request) -> None:
        """Feed a resolved request (finished, tombstoned, or preempted)
        into the attached observability layer — the per-request TTFT/TPOT
        event stream of `repro.obs.EngineObs.record_request`."""
        if self._obs is not None:
            self._obs.record_request(r)

    def _record_round(self, sample: dict) -> None:
        self._last_samples = [sample]
        self._trace.ingest_sample(sample)
        if self._obs is not None:
            self._obs.record_round(sample)

    def _host_sample(self, rnd: int, now_r: float, a0: int, e0: int,
                     p0: int, n_tok: int) -> dict:
        """Assemble the host `step()` round's telemetry sample — the SAME
        record (keys and values) `engine_state.ring_samples` drains from a
        megastep's in-scan :class:`TelemetryRing`, mirrored purely from the
        host bookkeeping.  The bit-identity property of tests/test_obs.py
        compares these with ``==`` across K rounds; extend both sides or
        neither (see `engine_state.TelemetrySample`)."""
        from . import sentinels
        from .engine_state import SLOT_TABLE

        if self._tenants is not None:
            # wrap-safe per-tenant credit: u32 difference re-read as i32
            # (the _sdist of core.functional — value survives 2³² wrap)
            count("d2h", 3)  # grant, consumed and dead
            credit = (np.asarray(self.qos.grant)
                      - np.asarray(self.qos.consumed)).view(np.int32)
            dead = np.asarray(self.qos.dead)
            backlog = int(self._tenant_live.sum())
        else:
            credit = np.zeros(0, np.int32)
            dead = np.zeros(0, np.uint32)
            backlog = len(self.backlog)
        paged = self._kv_pool is not None
        hist = np.zeros(SLOT_TABLE, np.int64)
        parked = pending = 0
        for r in self.active.values():
            if r.parked:
                parked += 1
                hist[r.park_bucket] += 1
            if self._chunk:
                plen = min(len(r.prompt), self._prompt_cap) or 1
                pending += max(plen - r.prefill_pos, 0)
        # per-round health bitmask — the host mirror of the in-scan
        # sentinel checks (serving.sentinels; megastep emits the same
        # field from `round_health` over the post-round device state)
        if self._chunk:
            if self._kv_share:
                # shared blocks are held ONCE — the refcount support is
                # the allocated set (conservation: free + live = NB), not
                # the per-slot table counts, which over-count sharers
                kv_held = int((self._kv_refcnt_h > 0).sum())
            else:
                kv_held = sum(r.kv_blocks for r in self.active.values())
        elif paged:
            kv_held = sum(self._kv_demand(r) for r in self.active.values())
        else:
            kv_held = 0
        health = sentinels.host_round_health(
            n_slots=self.n_slots, free_slots=len(self.free_slots),
            active=len(self.active), credit=credit, paged=paged,
            kv_free=int(self._kv_free_blocks) if paged else 0,
            kv_held=kv_held,
            kv_blocks=self._kv_blocks if paged else 0,
            chunked=self._chunk > 0,
            headroom=(self._kv_headroom(
                share=self._kv_share_state() if self._kv_share else None)
                if self._chunk else 0),
            stuck=(self._watchdog > 0 and any(
                rnd - r.last_adv_round >= self._watchdog
                for r in self.active.values())),
            nonfinite=self._round_nonfinite)
        return {
            "round": rnd,
            "clock": float(now_r),
            "admits": self.stats.admitted - a0,
            "expires": (self.stats.expired - e0)
            - (self.stats.preempted - p0),
            "preempts": self.stats.preempted - p0,
            "tokens": n_tok,
            "prefill_tokens": self._round_prefill_tokens,
            "prefill_chunks": self._round_prefill_chunks,
            "prefill_pending": pending,
            "gate_stalls": self._round_gate_stalls,
            "parked": parked,
            "backlog": backlog,
            "active": len(self.active),
            "slot_free": len(self.free_slots),
            "kv_free": int(self._kv_free_blocks) if paged else 0,
            "kv_pokes": (int(np.sum(np.asarray(self._kv_sema.bucket_seq),
                                    dtype=np.uint32)) if paged else 0),
            "prefix_hits": self._round_prefix_hits,
            "blocks_shared": (int((self._kv_refcnt_h >= 2).sum())
                              if self._kv_share else 0),
            "cow_copies": self._round_cow_copies,
            "health": int(health),
            "credit": [int(c) for c in credit],
            "poke_dead": [int(d) for d in dead],
            "kv_wait_hist": [int(h) for h in hist],
            # per-kind lists flattened in the canonical segment order
            # (serving.events.SCAN_SEGMENTS) — the exact list the device
            # event table drains after its stable compaction
            "events": (self._ev_preempt + self._ev_admit
                       + self._ev_attach + self._ev_park + self._ev_resume
                       + self._ev_chunk + self._ev_cow + self._ev_finish),
        }

    def telemetry(self) -> dict:
        """Gauge snapshot of the engine — pure host-side reads.

        Contract:

        * **No hidden host syncs.**  Every gauge comes off host bookkeeping
          (the counter mirrors) — calling ``telemetry()`` never transfers
          device arrays and never bumps ``stats.host_syncs``, which counts
          host rounds (one per ``step()``, one per ``megastep()``), not
          transfers.  The megastep's samples ride its one drain; a host
          ``step()`` builds its sample from three QoS arrays read back from
          the device, among the several blocking device→host readbacks of
          a step that the ``"d2h"`` phase count records
          (`repro.obs.trace.count`, under a profiler session).
        * **``pool_utilization`` is ALWAYS present**: a float in [0, 1]
          (blocks actually holding tokens / pool) for block-paged engines,
          and exactly ``None`` for dense engines — callers branch on the
          value, never on key presence.  The other block-pool gauges
          (``kv_blocks_free``, ``kv_blocks_live``, ``kv_block_stalls``,
          ``prefill_chunks``, ``parked_slots``) remain paged-only keys.
        * ``last_samples`` is the most recent serving call's per-round
          telemetry: ONE sample for a host ``step()``, K ring samples for a
          ``megastep(K)`` — identical record shape either way
          (`engine_state.ring_samples`).
        * With an ``obs=`` layer attached, ``slo`` carries its per-tenant
          TTFT/TPOT/attainment summary (`repro.obs.EngineObs.summary`).
        """
        tel = {
            "backlog": len(self.backlog),
            "active": len(self.active),
            "free_slots": len(self.free_slots),
            "queue_depth": max(0, self._sema_ticket_h - self._sema_grant_h),
            "stats": self.stats.__dict__.copy(),
            "pool_utilization": None,  # dense: no pool (see docstring)
            "last_samples": list(self._last_samples),
            # recovery-ladder action counters (repro.resilience) — every
            # containment/repair the engine performed, by rung
            "recovery": {
                "quarantined": self.stats.quarantined,
                "requeued": self.stats.requeued,
                "kv_audits": self.stats.kv_audits,
                "kernel_fallbacks": self.stats.kernel_fallbacks,
                "snapshots": self.stats.snapshots,
                "restores": self.stats.restores,
            },
            # per-request span trees + critical-path breakdown off the
            # host trace buffer (repro.obs.trace) — pure host reads, the
            # no-hidden-sync contract above covers this key too
            "trace": self._trace.summary(),
        }
        if self._kv_pool is not None:
            # block-pool gauges (the block semaphore's counter identity):
            # free = unreserved pool blocks, live = reserved blocks (whole
            # worst-case demand up-front; only the taken blocks in chunked
            # mode)
            tel["kv_blocks_free"] = int(self._kv_free_blocks)
            tel["kv_blocks_live"] = int(self._kv_blocks
                                        - self._kv_free_blocks)
            # pool_utilization = blocks actually HOLDING tokens / pool —
            # the gap to kv_blocks_live is the reservation waste the
            # chunked-incremental mode exists to reclaim
            written = 0
            for r in self.active.values():
                plen = min(len(r.prompt), self._prompt_cap) or 1
                cur = (r.prefill_pos if self._chunk and r.prefill_pos < plen
                       else plen + len(r.out_tokens))
                written += -(-cur // self._kv_bs) if cur else 0
            tel["pool_utilization"] = written / self._kv_blocks
            if self._kv_share:
                # under sharing the per-request sum above counts a shared
                # block once per SHARER — the refcount support is the
                # unique allocated set.  Read off the np refcnt mirror
                # (updated by _hshare_sync / the megastep carry), never
                # the device pool: the no-sync contract holds.
                live = int((self._kv_refcnt_h > 0).sum())
                tel["pool_utilization"] = live / self._kv_blocks
                tel["blocks_shared"] = int((self._kv_refcnt_h >= 2).sum())
                tel["prefix_hits"] = self.stats.prefix_hits
                tel["cow_copies"] = self.stats.cow_copies
            tel["kv_block_stalls"] = self.stats.kv_block_stalls
            tel["prefill_chunks"] = self.stats.prefill_chunks
            tel["parked_slots"] = sum(r.parked for r in self.active.values())
        if self._tenants is not None:
            total = sum(self.tenant_admitted.values())
            tel["backlog"] = int(self._tenant_live.sum())
            # the global `self.sema` is unused in QoS mode — queue depth is
            # the live per-tenant backlog, not the (frozen) ticket − grant
            tel["queue_depth"] = int(self._tenant_live.sum())
            tel["tenants"] = {
                t: {"weight": self._tenants[t],
                    "admitted": self.tenant_admitted[t],
                    "expired": self.tenant_expired[t],
                    "share": (self.tenant_admitted[t] / total) if total else 0.0,
                    "queue_depth": int(self._tenant_live[self._tindex[t]])}
                for t in self._tenant_names
            }
        if self._obs is not None:
            tel["slo"] = self._obs.summary()
        return tel
