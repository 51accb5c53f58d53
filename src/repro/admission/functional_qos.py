"""Batched multi-tenant QoS admission — the in-graph counterpart.

Extends `core.functional`'s MultiSemaState with per-tenant **weights**,
**deadline masks**, and a **tombstone-transparent admission rule**, so a
whole multi-tenant admission round (expire → admit → replenish → poke)
is one vectorized pass under jit — the reference semantics for a future
Pallas variant in `kernels/` (same role `core.functional` plays for
`kernels/sema_batch`).

State (all per-tenant vectors of length S, plus one shared waiting array):

  ticket / grant — the paper's counters, per tenant.  ``grant`` advances
      only via weighted replenishment from the global slot pool.
  consumed       — grant units actually used by admitted live rows;
      ``avail = grant − consumed`` is a tenant's spendable credit.
  dead           — tombstoned (deadline-expired / cancelled) tickets not
      yet absorbed by reclaim (dead-below-frontier slack); widens the
      conservative bucket-poke window, exactly generalizing `post_batch`'s
      ``[grant, grant+n)`` window (reduces to it when dead == 0), and
      decays as reclaim burns the credit those tombstones stranded — the
      poke cost no longer grows monotonically with total expirations.
  weight / vpass — stride scheduler: granting a unit advances the
      tenant's virtual pass by 1/weight; free units flow to the
      minimum-pass tenant with unmet live demand, so admission shares
      converge to the weights under saturation.
  bucket_seq     — ONE waiting array shared by all S tenant semaphores
      (paper §1: the array is process-global); tenants are dispersed by
      salting the TWA hash per tenant.

The admission rule is the batched tombstone-skip: a live row is admitted
iff its FCFS rank *among live rows of its tenant* is below the tenant's
avail — dead tickets anywhere in the queue (head, middle, or deep) are
transparent, so grant units always reach the earliest live waiters and
FCFS among live tickets is preserved (`core.functional.live_fifo_rank`).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.functional import (
    _sdist,
    live_fifo_rank,
    segment_counts,
    twa_hash_u32,
)
from ..core.hashfn import TICKET_STRIDE, MIX32KA

DEFAULT_TABLE_SIZE = 1024

# 17⁻¹ mod 2³² — reduced mod any power-of-two table size it stays the
# inverse, so ((bucket − start)·STRIDE_INV) mod T recovers a ticket's offset
# within a poke window (the coprime-stride permutation, cf. kernels/sema_batch).
STRIDE_INV = pow(TICKET_STRIDE, -1, 1 << 32)


class QoSState(NamedTuple):
    ticket: jax.Array  # (S,) u32 — per-tenant tickets issued
    grant: jax.Array  # (S,) u32 — per-tenant units replenished
    consumed: jax.Array  # (S,) u32 — units spent on admitted live rows
    dead: jax.Array  # (S,) u32 — tombstoned tickets (poke-window slack)
    weight: jax.Array  # (S,) f32 — QoS weights
    vpass: jax.Array  # (S,) f32 — stride virtual pass
    bucket_seq: jax.Array  # (T,) u32 — shared waiting array
    salt: jax.Array  # u32


def make_qos(weights, table_size: int = DEFAULT_TABLE_SIZE,
             salt: int = 0x9E3779B9) -> QoSState:
    """Weights must be ≥ 0.  A zero-weight tenant is granted at most ONE
    unit ever (its first virtual-pass crossing), after which its pass
    saturates to +inf and it starves — an intentional floor semantics for
    best-effort tiers; serving engines should validate weights > 0 (the
    `ContinuousBatchingEngine` does)."""
    w = jnp.asarray(weights, jnp.float32)
    assert table_size > 0 and (table_size & (table_size - 1)) == 0
    z = jnp.zeros_like(w, dtype=jnp.uint32)
    return QoSState(ticket=z, grant=z, consumed=z, dead=z, weight=w,
                    vpass=jnp.zeros_like(w),
                    bucket_seq=jnp.zeros((table_size,), jnp.uint32),
                    salt=jnp.uint32(salt))


def tenant_salt(state: QoSState, tenant_ids) -> jax.Array:
    """Per-tenant TWAHash salt — disperses the S logical semaphores over
    the one shared array (the `uintptr_t(L)` component, per tenant)."""
    t = jnp.asarray(tenant_ids, jnp.uint32)
    return state.salt + (t + jnp.uint32(1)) * jnp.uint32(MIX32KA)


def qos_bucket_index(state: QoSState, tenant_ids, tickets) -> jax.Array:
    table = state.bucket_seq.shape[-1]
    h = twa_hash_u32(tenant_salt(state, tenant_ids),
                     jnp.asarray(tickets, jnp.uint32))
    return (h & jnp.uint32(table - 1)).astype(jnp.int32)


def avail(state: QoSState) -> jax.Array:
    """Spendable grant units per tenant (int32, ≥ 0 by invariant)."""
    return _sdist(state.grant, state.consumed)


# -- take ---------------------------------------------------------------------


def qos_take(state: QoSState, tenant_ids: jax.Array, mask: jax.Array,
             deadlines: jax.Array | None = None, now=0.0):
    """Batched ticket issuance for N arrivals against S tenants.

    Rows whose deadline already passed at arrival are *dead on arrival*:
    they receive no ticket and are reported in ``expired``.  Returns
    ``(state', tickets, buckets, expired)``; admission is decided by
    :func:`qos_admit` (rank among live waiters), not at take time.

    Precision note: deadlines/now compare in float32 under default jax —
    pass RELATIVE times (deltas from a caller-held epoch), not absolute
    wall/monotonic stamps, which lose sub-second resolution at ~1e6 s.
    """
    tenant_ids = jnp.asarray(tenant_ids, jnp.int32)
    if deadlines is None:
        expired = jnp.zeros(mask.shape, bool)
    else:
        expired = mask & (jnp.asarray(deadlines) <= now)
    eff = mask & ~expired
    S = state.ticket.shape[0]
    onehot = jax.nn.one_hot(tenant_ids, S, dtype=jnp.uint32) * \
        eff[:, None].astype(jnp.uint32)
    ranks = (jnp.cumsum(onehot, axis=0) - onehot)  # exclusive, per tenant
    my_rank = jnp.take_along_axis(ranks, tenant_ids[:, None], axis=1)[:, 0]
    tickets = state.ticket[tenant_ids] + my_rank
    new_ticket = state.ticket + segment_counts(tenant_ids, eff, S)
    buckets = qos_bucket_index(state, tenant_ids, tickets)
    return state._replace(ticket=new_ticket), tickets, buckets, expired


# -- expire (tombstone) --------------------------------------------------------


def qos_expire(state: QoSState, tenant_ids: jax.Array, alive: jax.Array,
               deadlines: jax.Array, now):
    """Tombstone waiting rows whose deadline passed: they leave the live
    set (skip-transparent to later admissions) and widen the poke window.
    Returns ``(state', alive', newly_expired)``."""
    tenant_ids = jnp.asarray(tenant_ids, jnp.int32)
    newly = alive & (jnp.asarray(deadlines) <= now)
    per_tenant = segment_counts(tenant_ids, newly, state.ticket.shape[0])
    return state._replace(dead=state.dead + per_tenant), alive & ~newly, newly


# -- admit --------------------------------------------------------------------


def qos_admit(state: QoSState, tenant_ids: jax.Array, tickets: jax.Array,
              alive: jax.Array):
    """Tombstone-transparent weighted-FCFS admission over the live backlog:
    row admitted ⇔ live_fifo_rank < avail[tenant] (the O(N·S/block)
    blocked prefix, `core.functional.live_fifo_rank`).  Consumes the
    units.  Returns ``(state', admitted)``."""
    tenant_ids = jnp.asarray(tenant_ids, jnp.int32)
    S = state.ticket.shape[0]
    tickets = jnp.asarray(tickets, jnp.uint32)
    rank = live_fifo_rank(tenant_ids, tickets, alive, S)
    admitted = alive & (rank < avail(state)[tenant_ids])
    spent = segment_counts(tenant_ids, admitted, S)
    return state._replace(consumed=state.consumed + spent), admitted


# -- replenish (weighted grant from the global pool) ---------------------------


def stride_alloc(vpass: jax.Array, weight: jax.Array, unmet: jax.Array,
                 free_units, max_units: int):
    """Closed-form stride allocation (no ``max_units``-length sequential
    loop): tenant s's k-th grant crosses virtual time ``vpass_s + k/w_s``,
    so the sequential argmin schedule is exactly the merge of S arithmetic
    sequences — take the first ``take`` crossings of the flattened (value,
    tenant, k) sort.  A stable argsort over the (S, max_units) crossing
    matrix reproduces the argmin tie-break (lowest tenant index first).

    Non-finite crossings (zero-weight tenants past their first unit, or a
    vpass already saturated to +inf) are never granted.  Returns
    ``alloc (S,) u32``.
    """
    free_units = jnp.asarray(free_units, jnp.int32)
    S = vpass.shape[0]
    U = max_units
    k = jax.lax.broadcasted_iota(jnp.float32, (S, U), 1)
    step = jnp.where(weight[:, None] > 0, k / weight[:, None], jnp.inf)
    step = jnp.where(k == 0, 0.0, step)  # k=0 crossing is vpass itself (0/0 guard)
    cross = jnp.where(k < unmet[:, None].astype(jnp.float32),
                      vpass[:, None] + step, jnp.inf)
    finite = jnp.isfinite(cross)
    take = jnp.minimum(
        jnp.minimum(jnp.maximum(free_units, 0), jnp.int32(U)),
        jnp.sum(finite).astype(jnp.int32))
    order = jnp.argsort(cross.reshape(-1), stable=True)  # ties → (s, k) lex
    rank = jnp.zeros((S * U,), jnp.int32).at[order].set(
        jnp.arange(S * U, dtype=jnp.int32))
    granted = (rank < take).reshape(S, U)
    return jnp.sum(granted, axis=1).astype(jnp.uint32)


def poke_bump(state: QoSState, widths: jax.Array) -> jax.Array:
    """Waiting-array bump for per-tenant windows ``[grant_s, grant_s+w_s)``
    via the coprime-stride permutation (the `kernels/sema_batch` trick):
    ticket ``grant_s + k`` hashes to bucket ``(start_s + 17k) mod T``, and
    17 is coprime with the power-of-two table, so inverting the stride
    recovers each bucket's window offset — ``bump[j] = Σ_s [((j − start_s)
    · 17⁻¹ mod T) < w_s]``.  A dense compare instead of the former (S, T)
    hash-index matrix + scatter-add; windows ≥ T degrade to a full-table
    poke (never a missed poke), exactly as before."""
    table = state.bucket_seq.shape[-1]
    S = state.ticket.shape[0]
    start = twa_hash_u32(
        tenant_salt(state, jnp.arange(S, dtype=jnp.uint32)), state.grant)
    j = jnp.arange(table, dtype=jnp.uint32)[None, :]
    offs = ((j - start[:, None]) * jnp.uint32(STRIDE_INV)) & jnp.uint32(table - 1)
    return jnp.sum((offs < widths[:, None]).astype(jnp.uint32), axis=0)


def qos_replenish(state: QoSState, free_units, live_depth: jax.Array,
                  max_units: int):
    """Distribute up to ``free_units`` global slots by stride scheduling to
    tenants with unmet live demand; bump the TWAHash buckets of the
    conservatively-enabled ticket window (alloc + dead slack per tenant).

    ``max_units`` statically bounds the per-tenant grant count (engine:
    total slot count).  Returns ``(state', alloc, leftover)`` —
    ``leftover`` units stay in the caller's pool (work conservation).
    """
    free_units = jnp.asarray(free_units, jnp.int32)
    live_depth = jnp.asarray(live_depth, jnp.int32)
    unmet = jnp.clip(live_depth - avail(state), 0, max_units)
    alloc = stride_alloc(state.vpass, state.weight, unmet, free_units,
                         max_units)
    af = alloc.astype(jnp.float32)
    dv = jnp.where(alloc > 0,
                   jnp.where(state.weight > 0, af / state.weight, jnp.inf),
                   0.0)
    vpass = state.vpass + dv
    leftover = free_units - jnp.sum(alloc).astype(jnp.int32)

    # Conservative successor poke: newly enabled live tickets of tenant s
    # lie in [grant_s, grant_s + alloc_s + dead_s) — every not-yet-reclaimed
    # dead ticket can shift the live frontier up by one (``dead`` decays as
    # reclaim absorbs tombstone-stranded credit — see `qos_reclaim`).
    # Spurious pokes are benign (paper: collisions cause extra re-checks
    # only).  The window is clamped to the issued-ticket frontier: no
    # waiter holds a ticket ≥ `ticket`.
    outstanding = jnp.maximum(_sdist(state.ticket, state.grant), 0)
    width = jnp.minimum((alloc + state.dead).astype(jnp.int32),
                        outstanding).astype(jnp.uint32)
    bump = poke_bump(state, width)
    return state._replace(grant=state.grant + alloc, vpass=vpass,
                          bucket_seq=state.bucket_seq + bump), alloc, leftover


def qos_reclaim(state: QoSState, live_depth: jax.Array):
    """Burn surplus credit (granted past all live demand — stranded by
    tombstones) back to the caller's pool.  Returns ``(state', units)``.

    Each reclaimed unit is credit the grant frontier carried past a dead
    ticket, so that ticket can no longer displace a future enabled window:
    the poke slack ``dead`` shrinks by the reclaimed amount (saturating).
    This is the dead-below-frontier accounting — the window cost decays as
    the tombstone backlog drains instead of growing monotonically with
    total expirations."""
    live_depth = jnp.asarray(live_depth, jnp.int32)
    surplus = jnp.maximum(avail(state) - live_depth, 0).astype(jnp.uint32)
    return (state._replace(consumed=state.consumed + surplus,
                           dead=state.dead - jnp.minimum(state.dead, surplus)),
            jnp.sum(surplus).astype(jnp.int32))


# -- multi-resource gate (slots × KV blocks) -----------------------------------


def block_gate(admitted: jax.Array, demand: jax.Array, key: jax.Array,
               free_blocks, headroom=0, commit_demand=None, commit_free=0,
               commit_bootstrap=False):
    """Second-resource admission gate: of the rows the QoS round admitted
    (each holding one SLOT unit), keep the longest FCFS prefix whose
    cumulative **block** demand fits the free pool — the batched form of
    taking ``demand_i`` units from the TWA block semaphore in ticket
    order.  Strict FCFS: a row that does not fit blocks every later row
    (no bypass — a stream of small sequences can never starve a large
    one, exactly the paper's first-come-first-enabled order).

    ``key`` is the global admission order (the engine's packed
    (clamped ticket distance, tenant index) sort key — see
    `serving.engine_state._fcfs_key`); non-admitted rows must carry the
    sentinel INT32_MAX.  Returns the granted mask; the caller refunds the
    QoS slot credit of ``admitted & ~granted`` rows (they stay live in the
    backlog and retry next round — "block-stalled").

    ``headroom`` is the **reserved-headroom check** of the chunked-prefill
    subsystem (incremental allocation): demands are then FIRST-CHUNK
    demands, and the gate admits only into ``free − headroom``, where
    headroom = :func:`block_headroom` over the running slots — the blocks
    the safety-chain-earliest running sequences may still claim to
    finish.  Admission can therefore never eat into the reserve that
    keeps at least one runnable slot able to complete (the no-deadlock
    invariant documented in `serving.engine_state`); the worst-case
    up-front mode passes 0 (its demands are already whole-lifetime
    reservations).

    ``commit_demand``/``commit_free`` add the **commitment watermark**
    (chunked mode): each candidate's whole-lifetime demand must also fit
    the remaining commitment budget ``W − Σ rem(running)``.  Unlike the
    up-front gate this is PIPELINED — remaining demand drains as running
    sequences write, so reservations overlap in time — but it bounds
    aggregate outstanding demand: an overcommitted pool degenerates into
    the safety chain serializing the endgame (one funded slot at a time),
    which costs more rounds than the extra residency buys.
    ``commit_bootstrap``
    (the "pool is uncommitted" flag) exempts the FCFS-FIRST candidate
    from the watermark so a request larger than W is still served — it
    waits, strict no-bypass, until the pool drains, then runs alone
    (no starvation; the submit-time check bounds it by the pool itself).
    """
    n = admitted.shape[0]
    demand = jnp.asarray(demand, jnp.int32)
    order = jnp.argsort(jnp.where(admitted, key, jnp.iinfo(jnp.int32).max),
                        stable=True)
    adm_s = admitted[order]
    cum = jnp.cumsum(jnp.where(adm_s, demand[order], 0))
    fits = cum <= (jnp.asarray(free_blocks, jnp.int32)
                   - jnp.asarray(headroom, jnp.int32))
    if commit_demand is not None:
        cum2 = jnp.cumsum(jnp.where(adm_s,
                                    jnp.asarray(commit_demand,
                                                jnp.int32)[order], 0))
        first = adm_s & (jnp.cumsum(adm_s.astype(jnp.int32)) == 1)
        fits &= ((cum2 <= jnp.asarray(commit_free, jnp.int32))
                 | (first & commit_bootstrap))
    blocked = jnp.cumsum((adm_s & ~fits).astype(jnp.int32)) > 0
    ok = adm_s & fits & ~blocked
    return jnp.zeros((n,), bool).at[order].set(ok)


def block_headroom(rem: jax.Array, held: jax.Array, order: jax.Array,
                   active: jax.Array) -> jax.Array:
    """Reserved headroom of the incremental block allocator — the Banker
    margin that makes mid-sequence stalls parks instead of deadlocks.

    The chunked-prefill subsystem maintains, for live slots in priority
    order (earliest admission first), the safety invariant

        rem_i  ≤  free  +  Σ_{j<i} held_j        for every live slot i,

    i.e. every slot's worst-case *remaining* block demand is covered by
    the free pool plus everything its priority-predecessors will
    eventually release.  Under it the priority-first slot can always take
    (rem_1 ≤ free), so it never parks, finishes, and releases — the next
    slot inherits the cover (rem_2 ≤ free + held_1), and by induction
    every parked slot is resumed: a strict no-deadlock guarantee.

    ``headroom = max(0, max_i(rem_i − Σ_{j<i} held_j))`` is the smallest
    free-pool level that keeps the invariant; admission (`block_gate`) and
    incremental takes (`serving.prefill.chunk_plan`) both refuse to let
    ``free`` drop below it.  ``rem``/``held``: per-slot remaining demand /
    blocks held; ``order``: the priority permutation (e.g.
    `serving.prefill.banker_order` — earliest admission first); inactive
    rows are ignored.  Returns an i32 scalar.
    """
    rem = jnp.asarray(rem, jnp.int32)
    held = jnp.asarray(held, jnp.int32)
    order = jnp.asarray(order, jnp.int32)
    act_s = active[order]
    held_s = jnp.where(act_s, held[order], 0)
    cum_held = jnp.cumsum(held_s) - held_s
    deficit = jnp.where(act_s, rem[order] - cum_held,
                        jnp.iinfo(jnp.int32).min)
    return jnp.maximum(jnp.max(deficit, initial=0), 0)


# -- one fused admission round -------------------------------------------------


def qos_scan_round(state: QoSState, tenant_ids: jax.Array,
                   tickets: jax.Array, alive: jax.Array,
                   deadlines: jax.Array, now, free_pool, released,
                   max_units: int, *, round_impl=None):
    """One admission round with **slot-release feedback**: ``released``
    units freed by decode completions/preemptions *this* round re-enter
    the pool consumed by the SAME round's weighted replenish, so a slot
    reclaimed mid-scan is re-granted to the next live ticket without a
    host round-trip (the megastep's in-graph counterpart of the engine's
    ``_replenish_qos(freed)``).

    ``round_impl`` selects the round implementation (default
    :func:`qos_round`; the scheduler substitutes the fused Pallas pass
    `kernels.qos_admission.qos_round_fused` on TPU — bit-identical).
    Returns ``(state', admitted, expired, leftover_units)``.
    """
    free = (jnp.asarray(free_pool, jnp.int32)
            + jnp.asarray(released, jnp.int32))
    impl = round_impl if round_impl is not None else qos_round
    return impl(state, tenant_ids, tickets, alive, deadlines, now, free,
                max_units)


def qos_round(state: QoSState, tenant_ids: jax.Array, tickets: jax.Array,
              alive: jax.Array, deadlines: jax.Array, now, free_units,
              max_units: int):
    """One whole multi-tenant admission round as a single jit-able pass:
    expire → replenish (weighted) → admit (tombstone-transparent FCFS) →
    reclaim stranded credit.  Returns
    ``(state', admitted, expired, leftover_units)``.

    This is the oracle semantics for the fused Pallas kernel
    (`kernels.qos_admission.qos_round_fused`)."""
    tenant_ids = jnp.asarray(tenant_ids, jnp.int32)
    state, alive, expired = qos_expire(state, tenant_ids, alive, deadlines, now)
    S = state.ticket.shape[0]
    depth = segment_counts(tenant_ids, alive, S, dtype=jnp.int32)
    state, _, leftover = qos_replenish(state, free_units, depth, max_units)
    state, admitted = qos_admit(state, tenant_ids, tickets, alive)
    depth_after = depth - segment_counts(tenant_ids, admitted, S,
                                         dtype=jnp.int32)
    state, reclaimed = qos_reclaim(state, depth_after)
    return state, admitted, expired, leftover + reclaimed
