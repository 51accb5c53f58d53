"""L2 — the paper's semaphore as a *functional, batched* JAX construct.

TPUs have no shared-memory atomics inside a jitted program, so the paper's
per-thread ``fetch_add`` linearization is adapted: a *batch* of K concurrent
``take`` requests is linearized deterministically by row order, and their
tickets are ``base + exclusive_prefix_rank`` — one vectorized cumsum replaces
K atomic RMWs while preserving wait-free FCFS admission **for the batch
order** (which we make deterministic: arrival order = row index, exactly the
"first-come-first-enabled" order of the paper).

The waiting array maps to a `bucket_seq` vector: `post_batch` bumps the
TWAHash buckets of the granted ticket range (the scatter is the analogue of
the successor-of-successor poke), and a scheduler needs to re-examine *only*
requests whose bucket moved — the global-spinning analogue (re-scanning every
waiting request each step) is what this avoids.  `kernels/sema_batch`
implements the fused take+post+wake pass as a Pallas TPU kernel; this module
is its reference semantics and the pure-JAX fallback.

All counters are uint32 with wrap-safe int32 signed distances (sufficient
for < 2^31 outstanding distance; the paper's 200-year uint64 argument holds
a fortiori for per-run schedulers).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .hashfn import TICKET_STRIDE

DEFAULT_TABLE_SIZE = 1024


def next_pow2(n: int) -> int:
    """Smallest power of two ≥ n (shape-bucketing helper: the kernel
    backlog padding and the megastep (B, P) buckets must round the same
    way so steady-state serving reuses compiled executables)."""
    return 1 << max(n - 1, 0).bit_length()


class SemaState(NamedTuple):
    """One functional semaphore (or a vector of them if leading dims agree)."""

    ticket: jax.Array  # uint32 scalar
    grant: jax.Array  # uint32 scalar
    bucket_seq: jax.Array  # (table_size,) uint32 — waiting-array UpdateSequence
    salt: jax.Array  # uint32 scalar — the uintptr_t(L) component of TWAHash


def make_sema(count: int, table_size: int = DEFAULT_TABLE_SIZE, salt: int = 0x9E3779B9) -> SemaState:
    assert table_size > 0 and (table_size & (table_size - 1)) == 0
    return SemaState(
        ticket=jnp.uint32(0),
        grant=jnp.uint32(count),
        bucket_seq=jnp.zeros((table_size,), jnp.uint32),
        salt=jnp.uint32(salt),
    )


def _sdist(grant, ticket):
    """Signed distance grant - ticket under uint32 wrap (paper's int64_t dx)."""
    return (grant - ticket).astype(jnp.int32)


def twa_hash_u32(salt, ticket):
    return (salt + ticket * jnp.uint32(TICKET_STRIDE)).astype(jnp.uint32)


def bucket_index(state: SemaState, ticket) -> jax.Array:
    table = state.bucket_seq.shape[-1]
    return (twa_hash_u32(state.salt, ticket) & jnp.uint32(table - 1)).astype(jnp.int32)


def take_batch(state: SemaState, requests: jax.Array):
    """Batched SemaTake.

    requests: (N,) bool — which rows are taking (batch arrival order = FIFO
    order).  Returns (state', tickets (N,) u32, admitted (N,) bool,
    buckets (N,) i32).  Non-admitted requesters are "long-term waiters": the
    caller holds their ticket and their TWAHash bucket, and should re-check
    them only when their bucket's sequence moves (see `woken_mask`).
    """
    req = requests.astype(jnp.uint32)
    ranks = jnp.cumsum(req) - req  # exclusive prefix rank
    tickets = state.ticket + ranks
    admitted = requests & (_sdist(state.grant, tickets) > 0)
    new_state = state._replace(ticket=state.ticket + jnp.sum(req).astype(jnp.uint32))
    return new_state, tickets, admitted, bucket_index(state, tickets)


def post_batch(state: SemaState, n) -> SemaState:
    """Batched SemaPost of `n` units: grant += n and poke the TWAHash buckets
    of the enabled ticket range [grant, grant+n) (successor staging)."""
    n = jnp.asarray(n, jnp.uint32)
    table = state.bucket_seq.shape[-1]
    # Enabled tickets grant..grant+n-1 → bucket scatter-add (masked iota over
    # a bounded window keeps this jit-static; window = table size is enough
    # because pokes beyond one table orbit alias anyway).
    offs = jnp.arange(table, dtype=jnp.uint32)
    enabled = offs < n
    idx = bucket_index(state, state.grant + offs)
    bump = jnp.zeros((table,), jnp.uint32).at[idx].add(enabled.astype(jnp.uint32))
    return state._replace(grant=state.grant + n, bucket_seq=state.bucket_seq + bump)


def woken_mask(state: SemaState, observed_seq: jax.Array, buckets: jax.Array) -> jax.Array:
    """TWA-style re-check gate: True for waiters whose bucket sequence moved
    since `observed_seq` (their KeyMonitor sample). Waiters with False need
    not be re-evaluated at all this step — the scheduler's analogue of NOT
    globally spinning."""
    return state.bucket_seq[buckets] != observed_seq


def poll(state: SemaState, tickets: jax.Array) -> jax.Array:
    """Grant check for specific tickets (the short-term 'spin on Grant')."""
    return _sdist(state.grant, tickets) > 0


# -- block-paged pool (TWA semaphore over a circular free queue) --------------


class BlockPool(NamedTuple):
    """Demand-paged block allocator gated by a TWA semaphore — the paper's
    counting semaphore where the *units are KV-cache blocks* and the
    semaphore counters double as the cursors of a circular free queue:

      * ``sema.ticket`` / ``sema.grant`` are the paper's counters; the
        physical free-block count is the counter identity
        ``grant − ticket`` (wrap-safe signed distance);
      * the free queue holds block *identities*: queue position ``p``
        (a u32 cursor value) stores its id at ``free_q[p mod NB]`` — an
        allocation at ticket ``t`` takes ids ``free_q[t..t+k)``, a release
        writes ids at ``free_q[grant..grant+k)`` and `post`s, poking the
        waiting-array buckets of the enabled ticket range exactly as any
        other post (a block release wakes block waiters).

    ``num_blocks`` must be a power of two so the queue-position arithmetic
    stays exact across the 2³² counter wrap (``(p mod 2³²) mod NB ==
    p mod NB`` iff NB | 2³² — same reasoning as the bucket table mask).

    Blocks are **refcounted** (prefix sharing, PR 9): one block may be
    referenced by several live tables at once — a shared prompt prefix is
    stored exactly once, each sharer holding one reference.  Allocation
    grants a block with refcount 1; `pool_incref` attaches an additional
    sharer; `pool_release` is decref-then-`post` — a released reference
    only re-enqueues the block id (and pokes the waiting array) when its
    refcount hits zero, i.e. the semaphore's `post` becomes CONDITIONAL
    on the last reference dying.  ``gen`` is a per-block generation
    stamp, bumped each time a block is freed, so weak references (the
    prefix cache) can detect reuse without holding a refcount.

    Conservation invariant (property-tested): the free-queue region
    ``{free_q[ticket..grant)}`` and the referenced set ``{b : refcnt[b] >
    0}`` partition ``{0..NB-1}``, and per block the number of live
    block-table references equals ``refcnt`` (``Σ table references =
    Σ refcnt``) — no block is ever lost, and aliasing is exactly the
    refcount, never accidental.
    """

    sema: SemaState    # ticket/grant u32 — free blocks = grant − ticket
    free_q: jax.Array  # (NB,) i32 — circular queue of free block ids
    refcnt: jax.Array  # (NB,) i32 — live references per block (0 = free)
    gen: jax.Array     # (NB,) u32 — bumped on free (weak-ref validity)


def make_block_pool(num_blocks: int, table_size: int = 64,
                    salt: int = 0x9E3779B9, start: int = 0) -> BlockPool:
    """Fresh pool: all blocks free.  ``start`` offsets both counters (and
    rotates the queue to match) so tests can park the cursors just below
    the 2³² wrap."""
    assert num_blocks > 0 and (num_blocks & (num_blocks - 1)) == 0, \
        "num_blocks must be a power of two (wrap-safe queue positions)"
    sema = make_sema(count=num_blocks, table_size=table_size, salt=salt)
    start = jnp.uint32(start)
    sema = sema._replace(ticket=sema.ticket + start, grant=sema.grant + start)
    ids = jnp.arange(num_blocks, dtype=jnp.int32)
    pos = ((start + jnp.arange(num_blocks, dtype=jnp.uint32))
           & jnp.uint32(num_blocks - 1)).astype(jnp.int32)
    return BlockPool(sema=sema,
                     free_q=jnp.zeros((num_blocks,), jnp.int32).at[pos].set(ids),
                     refcnt=jnp.zeros((num_blocks,), jnp.int32),
                     gen=jnp.zeros((num_blocks,), jnp.uint32))


def pool_free_count(pool: BlockPool) -> jax.Array:
    """Free blocks — the paper's counter identity, i32 scalar."""
    return _sdist(pool.sema.grant, pool.sema.ticket)


def pool_alloc(pool: BlockPool, counts: jax.Array, max_per: int):
    """Batched wrap-safe take: consumer ``s`` receives ``counts[s]`` block
    ids (its row of the returned ``(S, max_per)`` table, -1 padded), taken
    from the free queue in cursor order — consumers are linearized by row
    index, the batched FCFS of `take_batch`.  The caller must guarantee
    ``sum(counts) ≤ pool_free_count`` (the engine's admission gate does).
    Returns ``(pool', ids)``."""
    counts = jnp.asarray(counts, jnp.int32)
    NB = pool.free_q.shape[0]
    cum = jnp.cumsum(counts) - counts            # exclusive prefix offsets
    k = jnp.arange(max_per, dtype=jnp.int32)
    take = k[None, :] < counts[:, None]          # (S, max_per)
    pos = (pool.sema.ticket + cum[:, None].astype(jnp.uint32)
           + k[None, :].astype(jnp.uint32)) & jnp.uint32(NB - 1)
    ids = jnp.where(take, pool.free_q[pos.astype(jnp.int32)], -1)
    total = jnp.sum(counts).astype(jnp.uint32)
    sema = pool.sema._replace(ticket=pool.sema.ticket + total)
    refcnt = pool.refcnt.at[jnp.where(take, ids, NB)].add(
        take.astype(jnp.int32), mode="drop")   # fresh grant: refcount 0 → 1
    return pool._replace(sema=sema, refcnt=refcnt), ids


def pool_release(pool: BlockPool, ids: jax.Array, mask: jax.Array) -> BlockPool:
    """Batched decref-then-`post`: every non-negative id in the rows
    selected by ``mask`` drops one reference; a block re-enters the free
    queue at the grant cursor — and the semaphore `post`s, advancing
    grant AND poking the TWAHash buckets of the newly enabled ticket
    range — only when its refcount hits zero (the last sharer leaving).
    With no sharing (every refcount 1) this degenerates to the PR-4
    unconditional post.  Freed ids enqueue in ascending-id order (any
    fixed order preserves the partition invariant; ascending keeps the
    scatter deterministic when one batch frees several blocks).  Each
    freed block's ``gen`` stamp bumps, invalidating weak references.
    Refcounts are NOT clamped at zero: releasing a reference that was
    never held drives ``refcnt`` negative, which the partition sentinel
    (`serving.sentinels.kv_partition_violated`) reports as corruption —
    the double-release fault stays detectable."""
    NB = pool.free_q.shape[0]
    valid = mask[:, None] & (ids >= 0) if ids.ndim == 2 else mask & (ids >= 0)
    flat = ids.reshape(-1)
    tgt = jnp.where(valid.reshape(-1), flat, NB)  # out-of-range → dropped
    cnt = jnp.zeros((NB,), jnp.int32).at[tgt].add(1, mode="drop")
    refcnt = pool.refcnt - cnt
    freed = (cnt > 0) & (refcnt == 0)            # decref hit exactly zero
    fu = freed.astype(jnp.uint32)
    rank = jnp.cumsum(fu) - fu
    pos = ((pool.sema.grant + rank) & jnp.uint32(NB - 1)).astype(jnp.int32)
    qtgt = jnp.where(freed, pos, NB)
    free_q = pool.free_q.at[qtgt].set(jnp.arange(NB, dtype=jnp.int32),
                                      mode="drop")
    return BlockPool(sema=post_batch(pool.sema, jnp.sum(fu)), free_q=free_q,
                     refcnt=refcnt, gen=pool.gen + fu)


def pool_incref(pool: BlockPool, ids: jax.Array, mask: jax.Array) -> BlockPool:
    """Attach additional references (prefix sharing): every non-negative id
    selected by ``mask`` gains one reference.  No counter moves, no queue
    traffic, no poke — sharing an already-live block is free at the
    semaphore level; only the LAST `pool_release` of a block posts."""
    NB = pool.free_q.shape[0]
    valid = mask & (ids >= 0)
    tgt = jnp.where(valid, ids, NB).reshape(-1)
    refcnt = pool.refcnt.at[tgt].add(
        valid.reshape(-1).astype(jnp.int32), mode="drop")
    return pool._replace(refcnt=refcnt)


def park_state(sema: SemaState, deficit: jax.Array):
    """Waiting-array park registration for block waiters (the long-term wait
    of the paper, at pool granularity): a waiter short ``deficit`` units
    becomes runnable exactly when the grant cursor has advanced ``deficit``
    more places, i.e. when ticket ``grant + deficit − 1`` is enabled.
    Releases `post` and poke the buckets of the enabled range in order, so
    that ticket's TWAHash bucket moves precisely when cumulative releases
    reach the deficit — the waiter observes ``(bucket, seq)`` here and
    re-examines only when the bucket's sequence moves (`woken_mask`).
    Hash aliasing can wake early (the paper's benign spurious re-check);
    a woken waiter whose re-check fails re-parks with a fresh deficit.
    Returns ``(bucket (…,) i32, observed_seq (…,) u32)``."""
    wake = sema.grant + jnp.asarray(deficit, jnp.uint32) - jnp.uint32(1)
    bucket = bucket_index(sema, wake)
    return bucket, sema.bucket_seq[bucket]


def pool_try_alloc(pool: BlockPool, counts: jax.Array, max_per: int, *,
                   park: jax.Array, deficit: jax.Array):
    """Guarded batched take + waiting-array park — the incremental-allocation
    entry point (`serving.prefill.chunk_plan` decides the counts).

    ``counts`` rows take their blocks (a plain wrap-safe `pool_alloc`; the
    caller's no-deadlock plan guarantees they fit), while rows flagged in
    ``park`` register as block waiters instead of spinning on the free
    count: each parked row records the `park_state` of its ``deficit`` —
    the TWA bucket whose poke signals that enough releases have landed for
    a re-check.  This is the paper's long-term wait transplanted to block
    grants: a mid-sequence block stall costs one bucket observation, not a
    per-round rescan of every stalled slot, and resumes flow FCFS because
    releases enable tickets (and poke their buckets) strictly in cursor
    order.  Returns ``(pool', ids (S, max_per), bucket (S,), seq (S,))``
    — bucket/seq are meaningful only where ``park`` is set (0 elsewhere).
    """
    park = jnp.asarray(park, bool)
    new_pool, ids = pool_alloc(pool, counts, max_per)
    bucket, seq = park_state(pool.sema, jnp.maximum(jnp.asarray(deficit,
                                                                jnp.int32), 1))
    return (new_pool, ids, jnp.where(park, bucket, 0),
            jnp.where(park, seq, jnp.uint32(0)))


# -- vectorized multi-semaphore (one per expert / per resource class) ---------


class MultiSemaState(NamedTuple):
    ticket: jax.Array  # (S,) uint32
    grant: jax.Array  # (S,) uint32


def make_multi_sema(counts: jax.Array) -> MultiSemaState:
    counts = jnp.asarray(counts, jnp.uint32)
    return MultiSemaState(ticket=jnp.zeros_like(counts), grant=counts)


def take_batch_multi(state: MultiSemaState, sema_ids: jax.Array, mask: jax.Array,
                     block: int = 1024):
    """K requests against S semaphores in one pass (MoE capacity admission).

    sema_ids: (N,) int32 in [0,S); mask: (N,) bool.  Returns
    (state', tickets, admitted) where admitted[i] ⇔ rank within its
    semaphore's remaining grant.  Deterministic FCFS per semaphore ⇒ the
    paper's first-come-first-enabled order decides which tokens overflow.

    Per-semaphore FIFO ranks use a TWO-LEVEL blocked prefix (§Perf iteration
    3): rank = intra-block exclusive rank + carried per-block base — exactly
    the kernels/sema_batch structure (per-block tri-rank + carry).  A flat
    global `cumsum(one_hot)` lowers catastrophically under SPMD: measured
    1.58e14 flops/chip (≈4·N²) on deepseek train_4k — 20× the expert matmul
    cost; the blocked form is O(N·S).
    """
    S = state.ticket.shape[0]
    N = sema_ids.shape[0]
    pad = (-N) % block
    ids_p = jnp.pad(sema_ids, (0, pad))
    mask_p = jnp.pad(mask, (0, pad))
    nb = (N + pad) // block
    onehot = (jax.nn.one_hot(ids_p, S, dtype=jnp.uint32)
              * mask_p[:, None].astype(jnp.uint32)).reshape(nb, block, S)
    intra = jnp.cumsum(onehot, axis=1)  # (nb, block, S) inclusive within block
    block_tot = intra[:, -1, :]  # (nb, S)
    base = jnp.cumsum(block_tot, axis=0) - block_tot  # exclusive block base
    ranks = (base[:, None, :] + intra - onehot).reshape(-1, S)[:N]  # exclusive
    my_rank = jnp.take_along_axis(ranks, sema_ids[:, None], axis=1)[:, 0]
    tickets = state.ticket[sema_ids] + my_rank
    admitted = mask & (_sdist(state.grant[sema_ids], tickets) > 0)
    new_ticket = state.ticket + jnp.sum(block_tot, axis=0)
    return state._replace(ticket=new_ticket), tickets, admitted


def post_batch_multi(state: MultiSemaState, counts: jax.Array) -> MultiSemaState:
    return state._replace(grant=state.grant + jnp.asarray(counts, jnp.uint32))


def segment_counts(ids: jax.Array, mask: jax.Array, num_segments: int,
                   dtype=jnp.uint32) -> jax.Array:
    """Per-segment count of mask-true rows — the shared per-tenant reduction
    used throughout `admission.functional_qos` (take/expire/admit/round all
    need "how many flagged rows per tenant").  A segment-sum instead of the
    former ``sum(one_hot(ids) * mask)`` idiom: no (N, S) materialization."""
    return jax.ops.segment_sum(
        jnp.asarray(mask).astype(dtype), jnp.asarray(ids, jnp.int32),
        num_segments=num_segments)


def bucket_histogram(buckets: jax.Array, mask: jax.Array,
                     table_size: int) -> jax.Array:
    """Waiting-array occupancy histogram — the paper's observable: how many
    long-term waiters currently observe each TWAHash bucket.  ``buckets``
    are the waiters' observed bucket indices (e.g. ``Slots.park_bucket``),
    ``mask`` selects the rows that are actually parked.  A flat histogram
    means the salt disperses waiters well (bounded re-checks per poke); a
    spike is the hash-aliasing pathology the paper's salt term exists to
    avoid.  Returns (table_size,) i32."""
    return segment_counts(jnp.asarray(buckets, jnp.int32), mask, table_size,
                          dtype=jnp.int32)


def ticket_order(sema_ids: jax.Array, tickets: jax.Array,
                 num_semas: int) -> jax.Array:
    """Stable permutation putting every semaphore's rows in wrap-safe ticket
    order (cross-semaphore interleaving is arbitrary — per-semaphore prefix
    counts don't care).  The key is the signed ticket distance from the
    semaphore's first-seen ticket, valid while a semaphore's outstanding
    tickets span < 2³¹ (the module-wide counter invariant).  Shared by
    `live_fifo_rank` and the `kernels.qos_admission` wrapper — the two must
    sort identically for the kernel's bit-exactness."""
    n = tickets.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    sema_ids = jnp.asarray(sema_ids, jnp.int32)
    tickets = jnp.asarray(tickets, jnp.uint32)
    first_row = jnp.full((num_semas,), n, jnp.int32).at[sema_ids].min(
        jnp.arange(n, dtype=jnp.int32))
    ref = tickets[jnp.clip(first_row, 0, n - 1)]  # (S,) u32
    key = _sdist(tickets, ref[sema_ids])
    return jnp.argsort(key, stable=True)


def live_fifo_rank(sema_ids: jax.Array, tickets: jax.Array,
                   alive: jax.Array, num_semas: int,
                   block: int = 512) -> jax.Array:
    """Rank of each row among the *alive* rows of its semaphore, in ticket
    order — the batched form of the tombstone-skip: dead (cancelled /
    deadline-expired) tickets are transparent, so grant units flow to the
    earliest live waiters and FCFS among live tickets is preserved exactly.
    Dead rows get rank N (never admitted by a `< avail` test).

    O(N·S/block) two-level blocked prefix (the `take_batch_multi`
    structure) over a per-tenant ticket-order argsort:

      1. wrap-safe sort key: signed ticket distance from the tenant's
         first-seen ticket (valid while a tenant's outstanding tickets span
         < 2³¹ — the module-wide counter invariant);
      2. stable argsort puts every tenant's rows in ticket order (ties
         across tenants are irrelevant — counts are per tenant);
      3. alive-masked (nb, block, S) one-hot two-level prefix gives each
         sorted row its exclusive count of earlier live same-tenant rows;
      4. scatter back through the inverse permutation.

    The former O(N²) pairwise comparison is kept as
    :func:`live_fifo_rank_pairwise`, the oracle of the equivalence tests.
    Tickets are assumed unique within a tenant (they are consecutive
    counter values by construction).
    """
    n = tickets.shape[0]
    sema_ids = jnp.asarray(sema_ids, jnp.int32)
    tickets = jnp.asarray(tickets, jnp.uint32)
    S = num_semas
    order = ticket_order(sema_ids, tickets, S)

    ids_s = sema_ids[order]
    alive_s = alive[order]
    pad = (-n) % block
    ids_p = jnp.pad(ids_s, (0, pad))
    alive_p = jnp.pad(alive_s, (0, pad))
    nb = (n + pad) // block
    onehot = (jax.nn.one_hot(ids_p, S, dtype=jnp.uint32)
              * alive_p[:, None].astype(jnp.uint32)).reshape(nb, block, S)
    intra = jnp.cumsum(onehot, axis=1)  # inclusive within block
    block_tot = intra[:, -1, :]  # (nb, S)
    base = jnp.cumsum(block_tot, axis=0) - block_tot  # exclusive block base
    ranks = (base[:, None, :] + intra - onehot).reshape(-1, S)[:n]
    my = jnp.take_along_axis(ranks, ids_s[:, None], axis=1)[:, 0]
    rank = jnp.zeros((n,), jnp.int32).at[order].set(my.astype(jnp.int32))
    return jnp.where(alive, rank, jnp.int32(n))


def live_fifo_rank_pairwise(sema_ids: jax.Array, tickets: jax.Array,
                            alive: jax.Array) -> jax.Array:
    """O(N²) pairwise-comparison form of :func:`live_fifo_rank` — retained
    as the equivalence oracle and the benchmark baseline the blocked-prefix
    path is measured against (BENCH trajectory: qos_round scaling)."""
    n = tickets.shape[0]
    same = sema_ids[:, None] == sema_ids[None, :]
    before = _sdist(tickets[:, None], tickets[None, :]) > 0  # ticket_j < ticket_i
    rank = jnp.sum(same & before & alive[None, :], axis=1).astype(jnp.int32)
    return jnp.where(alive, rank, jnp.int32(n))
