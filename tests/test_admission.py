"""Multi-tenant QoS admission subsystem (src/repro/admission/).

Covers the three ISSUE-mandated properties:
  * weighted fairness — tenant admission counts converge to weights under
    saturation (hierarchical tree with real threads AND the batched
    functional QoS state);
  * tombstone cancellation — a cancelled/expired waiter never consumes a
    slot and never blocks later live tickets; FCFS among live waiters is
    preserved exactly (host skip-aware post, handle cancel, functional
    live-rank, distributed KV lease);
  * deadline misses through ContinuousBatchingEngine — an expired backlog
    entry is tombstoned, its client unblocked, later requests unaffected.
"""

from __future__ import annotations

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

try:  # hypothesis is an optional test dependency (pyproject `test` extra)
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, strategies as st

from repro.admission import (
    CancellableTake,
    HierarchicalTWASemaphore,
    make_qos,
    qos_admit,
    qos_replenish,
    qos_round,
    qos_take,
    take_with_timeout,
)
from repro.core.twa_semaphore import TWASemaphore
from repro.runtime.coordinator import DistributedTicketLease, KVStore
from repro.serving.scheduler import ContinuousBatchingEngine, Request


# --------------------------------------------------------------- tombstones --


def test_tombstone_skip_preserves_live_fcfs():
    """Waiters A, B, C in FCFS order; B abandons.  Posts must reach A then
    C (skipping B's dead ticket) in ticket order — the skip-aware post."""
    sem = TWASemaphore(0, waiting="futex", cancellation=True)
    order: list[str] = []
    order_lock = threading.Lock()

    def waiter(name):
        sem.take()
        with order_lock:
            order.append(name)

    a = threading.Thread(target=waiter, args=("A",)); a.start()
    time.sleep(0.05)  # ticket order: A=0
    got_b = []
    b = threading.Thread(
        target=lambda: got_b.append(take_with_timeout(sem, 0.15)))
    b.start()  # B=1, will time out
    time.sleep(0.05)
    c = threading.Thread(target=waiter, args=("C",)); c.start()  # C=2
    b.join(3)
    assert got_b == [False]
    sem.post(1)  # → A
    a.join(3)
    sem.post(1)  # lands on B's tombstone → skipped → C
    c.join(3)
    assert order == ["A", "C"]
    assert sem.tombstones_skipped == 1
    assert sem.tombstones_pending() == 0


def test_cancel_lost_race_holds_slot():
    """A cancel that arrives after the grant reports 'acquired' — the slot
    is owned, never leaked, never double-granted."""
    sem = TWASemaphore(1, cancellation=True)
    assert sem.take_until(time.monotonic() - 1.0) is True  # grant pre-arrived
    assert sem.available() == 0
    sem.post()
    assert sem.available() == 1


def test_external_cancel_unblocks_futex_waiter():
    sem = TWASemaphore(0, waiting="futex", cancellation=True)
    handle = CancellableTake(sem)
    res = []
    t = threading.Thread(target=lambda: res.append(handle.wait(None)))
    t.start()
    time.sleep(0.1)
    assert handle.cancel() is True
    t.join(3)
    assert not t.is_alive() and res == [False]
    # the tombstone is transparent to the next waiter
    nxt = CancellableTake(sem)
    sem.post(1)
    assert nxt.wait(time.monotonic() + 3) is True


def test_cancel_exactly_one_outcome_under_race():
    """Hammer cancel-vs-post: for every handle exactly one of
    {acquired, cancelled} holds, and slots are conserved."""
    for trial in range(30):
        sem = TWASemaphore(0, cancellation=True)
        handles = [CancellableTake(sem) for _ in range(4)]
        results = [None] * 4

        def wait(i):
            results[i] = handles[i].wait(time.monotonic() + 0.01 * (i % 3))

        ts = [threading.Thread(target=wait, args=(i,)) for i in range(4)]
        [t.start() for t in ts]
        sem.post(2)
        [t.join(5) for t in ts]
        acquired = sum(bool(r) for r in results)
        # 2 units among 4 deadline-racing waiters: the acquired count plus
        # units still available (skipped past everyone) must equal 2.
        assert acquired + sem.available() == 2, (trial, results)


# --------------------------------------------------------- hierarchical tree --


def test_hierarchical_weighted_shares_under_saturation():
    """Tenant admission counts converge to weights while all tenants stay
    backlogged (stride replenishment)."""
    h = HierarchicalTWASemaphore(4, waiting="futex")
    weights = {"gold": 4.0, "silver": 2.0, "bronze": 1.0}
    for t, w in weights.items():
        h.register(t, w)
    stop = threading.Event()

    def worker(tenant):
        while not stop.is_set():
            if h.acquire(tenant, timeout=1.0):
                time.sleep(0.0005)
                h.release(tenant)

    ts = [threading.Thread(target=worker, args=(t,))
          for t in weights for _ in range(4)]
    [t.start() for t in ts]
    time.sleep(1.5)
    stop.set()
    [t.join(5) for t in ts]
    shares = h.shares()
    wsum = sum(weights.values())
    for t, w in weights.items():
        target = w / wsum
        assert abs(shares[t] - target) / target < 0.15, (shares, t)


def test_hierarchical_cancel_does_not_strand_slots():
    """A tenant whose only waiter abandons must not hoard the slot: it is
    reclaimed and flows to the other tenant (work conservation)."""
    h = HierarchicalTWASemaphore(1, waiting="futex")
    h.register("a", 1.0)
    h.register("b", 1.0)
    assert h.acquire("a", timeout=1.0)  # a holds the only slot
    res_b = []
    b = threading.Thread(target=lambda: res_b.append(
        h.acquire("b", timeout=0.15)))
    b.start()
    b.join(3)
    assert res_b == [False]  # b abandoned; its leaf may hold a stranded unit
    res_a2 = []
    a2 = threading.Thread(target=lambda: res_a2.append(
        h.acquire("a", timeout=5.0)))
    a2.start()
    time.sleep(0.05)
    h.release("a")  # must reach a2 despite b's tombstone
    a2.join(5)
    assert res_a2 == [True]
    h.release("a")
    tel = h.telemetry()
    assert tel["free"] == 1  # slot conserved back at the root


# ------------------------------------------------------------ functional QoS --


def test_qos_functional_weighted_split():
    s = make_qos([4.0, 2.0, 1.0], table_size=256)
    ids = jnp.asarray([0] * 8 + [1] * 8 + [2] * 8, jnp.int32)
    s, tickets, buckets, expired = qos_take(s, ids, jnp.ones(24, bool))
    assert not bool(expired.any())
    s, alloc, leftover = qos_replenish(
        s, 14, jnp.asarray([8, 8, 8], jnp.int32), max_units=16)
    np.testing.assert_array_equal(np.asarray(alloc), [8, 4, 2])
    assert int(leftover) == 0
    s, admitted = qos_admit(s, ids, tickets, jnp.ones(24, bool))
    counts = [int(admitted[np.asarray(ids) == i].sum()) for i in range(3)]
    assert counts == [8, 4, 2]


def test_qos_dead_ticket_transparent_fcfs():
    """A dead ticket in the MIDDLE of a tenant queue is skipped: grant
    units flow to the earliest live tickets, in ticket order."""
    s = make_qos([1.0], table_size=64)
    ids = jnp.zeros((4,), jnp.int32)
    s, tickets, _, _ = qos_take(s, ids, jnp.ones(4, bool))
    alive = jnp.asarray([True, False, True, True])  # ticket 1 tombstoned
    s = s._replace(dead=s.dead + jnp.asarray([1], jnp.uint32))
    s, alloc, _ = qos_replenish(s, 2, jnp.asarray([3], jnp.int32), max_units=4)
    assert int(alloc[0]) == 2
    s, admitted = qos_admit(s, ids, tickets, alive)
    # 2 units → tickets 0 and 2 (1 is dead, 3 waits) — live FCFS exact
    np.testing.assert_array_equal(np.asarray(admitted), [1, 0, 1, 0])


def test_qos_round_deadline_expiry():
    """qos_round: expired rows are tombstoned (reported, never admitted)
    and their would-be slots reach later live rows in the same pass."""
    s = make_qos([1.0, 1.0], table_size=64)
    ids = jnp.asarray([0, 0, 1, 1], jnp.int32)
    s, tickets, _, _ = qos_take(s, ids, jnp.ones(4, bool))
    deadlines = jnp.asarray([0.5, 10.0, 0.5, 10.0])
    s, admitted, expired, leftover = qos_round(
        s, ids, tickets, jnp.ones(4, bool), deadlines, now=1.0,
        free_units=2, max_units=4)
    np.testing.assert_array_equal(np.asarray(expired), [1, 0, 1, 0])
    np.testing.assert_array_equal(np.asarray(admitted), [0, 1, 0, 1])
    assert int(leftover) == 0


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(1, 8), min_size=2, max_size=5))
def test_qos_replenish_share_property(weights):
    """Property: distributing many units across always-backlogged tenants
    lands each tenant within one stride step of its weighted share."""
    S = len(weights)
    s = make_qos([float(w) for w in weights], table_size=64)
    units = 40
    depth = jnp.full((S,), units, jnp.int32)  # bottomless backlogs
    s, alloc, leftover = qos_replenish(s, units, depth, max_units=64)
    assert int(leftover) == 0
    total, wsum = int(jnp.sum(alloc)), sum(weights)
    assert total == units
    for i, w in enumerate(weights):
        target = units * w / wsum
        assert abs(int(alloc[i]) - target) <= wsum / min(weights) + 1, (
            np.asarray(alloc), weights)


# ------------------------------------------------------------------- engine --


def _run_engine(eng, reqs, max_steps=5000, until=None):
    steps = 0
    goal = until or (lambda: eng.stats.finished + eng.stats.expired >= len(reqs))
    while not goal() and steps < max_steps:
        eng.step(lambda lg: np.zeros(len(lg), np.int64))
        steps += 1
    return steps


def test_engine_weighted_fcfs_admission():
    """≥3 tenants of unequal weights: saturation-window admission shares
    within 10% of weights; FCFS within each tenant (admit time order ==
    submit order)."""
    weights = {"gold": 4.0, "silver": 2.0, "bronze": 1.0}
    eng = ContinuousBatchingEngine(
        lambda active: np.zeros(len(active)), lambda r: None, n_slots=6,
        tenants=weights)
    reqs, rid = [], 0
    for _ in range(100):
        for t in weights:
            reqs.append(Request(rid=rid, prompt=[1], max_new_tokens=3,
                                tenant_id=t))
            rid += 1
    eng.submit_batch(reqs)
    _run_engine(eng, reqs, until=lambda: not all(d > 0 for d in eng._tenant_live))
    total = sum(eng.tenant_admitted.values())
    wsum = sum(weights.values())
    for t, w in weights.items():
        target = w / wsum
        share = eng.tenant_admitted[t] / total
        assert abs(share - target) / target < 0.10, (t, share, target)
    # FCFS within tenant: admission timestamps follow ticket order
    for t in weights:
        admitted = [r for r in reqs if r.tenant_id == t and r.admit_t > 0]
        tks = [r.ticket for r in sorted(admitted, key=lambda r: r.admit_t)]
        assert tks == sorted(tks), t
    # TWA gating did real work: most backlog rows were never re-examined
    assert eng.stats.backlog_skipped > eng.stats.backlog_scans


@pytest.mark.parametrize("use_kernel", [False, True])
def test_engine_admission_shares_track_weights(use_kernel):
    """150 requests per tenant at weights 4:2:1 over 6 slots: while every
    tenant still has a backlog, each tenant's share of admissions is within
    10% of its weight's share, through the host queue walk and through the
    fused admission round alike."""
    weights = {"gold": 4.0, "silver": 2.0, "bronze": 1.0}
    eng = ContinuousBatchingEngine(
        lambda active: np.zeros(len(active)), lambda r: None, n_slots=6,
        tenants=weights, use_kernel=use_kernel)
    reqs, rid = [], 0
    for _ in range(150):
        for t in weights:
            reqs.append(Request(rid=rid, prompt=[1], max_new_tokens=3,
                                tenant_id=t))
            rid += 1
    eng.submit_batch(reqs)
    saturated = lambda: all(d > 0 for d in eng._tenant_live)
    _run_engine(eng, reqs, until=lambda: not saturated())
    assert not saturated()  # a backlog drained, not the step cap
    total = sum(eng.tenant_admitted.values())
    wsum = sum(weights.values())
    for t, w in weights.items():
        share = eng.tenant_admitted[t] / total
        assert abs(share - w / wsum) / (w / wsum) < 0.10, (t, share)


def test_engine_deadline_miss_tombstoned():
    """A queued request whose deadline passes is expired (client unblocked,
    stats counted) and never blocks later live requests of its tenant."""
    eng = ContinuousBatchingEngine(
        lambda active: np.zeros(len(active)), lambda r: None, n_slots=1,
        tenants={"a": 1.0})
    blocker = Request(rid=0, prompt=[1], max_new_tokens=40, tenant_id="a")
    doomed = Request(rid=1, prompt=[1], max_new_tokens=2, tenant_id="a",
                     deadline=time.monotonic() + 0.05)
    later = Request(rid=2, prompt=[1], max_new_tokens=2, tenant_id="a")
    eng.submit_batch([blocker, doomed, later])
    time.sleep(0.1)  # the doomed deadline passes while queued
    _run_engine(eng, [blocker, doomed, later])
    assert doomed.expired and doomed.done_event.is_set()
    assert doomed.admit_t == 0.0 and not doomed.out_tokens
    assert len(later.out_tokens) >= 2  # the tombstone never blocked it
    assert eng.stats.expired == 1 and eng.stats.finished == 2
    assert eng.telemetry()["tenants"]["a"]["expired"] == 1


def test_engine_dead_on_arrival():
    eng = ContinuousBatchingEngine(
        lambda active: np.zeros(len(active)), lambda r: None, n_slots=2,
        tenants={"a": 1.0})
    doa = Request(rid=0, prompt=[1], max_new_tokens=2, tenant_id="a",
                  deadline=time.monotonic() - 1.0)
    live = Request(rid=1, prompt=[1], max_new_tokens=2, tenant_id="a")
    eng.submit_batch([doa, live])
    _run_engine(eng, [doa, live])
    assert doa.expired and doa.done_event.is_set()
    assert len(live.out_tokens) >= 2
    assert eng.stats.expired == 1 and eng.stats.finished == 1


def test_engine_single_tenant_path_unchanged():
    """Legacy (no tenants=) admission still FCFS over one flat queue."""
    eng = ContinuousBatchingEngine(
        lambda active: np.zeros(len(active)), lambda r: None, n_slots=4)
    reqs = [Request(rid=i, prompt=[1], max_new_tokens=2) for i in range(32)]
    eng.submit_batch(reqs)
    _run_engine(eng, reqs)
    assert eng.stats.finished == 32
    tks = [r.ticket for r in sorted(reqs, key=lambda r: r.admit_t)]
    assert tks == sorted(tks)


# -------------------------------------------------------- distributed lease --


def test_lease_timeout_does_not_wedge_grant_sequence():
    """The ISSUE's cluster scenario: a dying host abandons its wait; the
    release path skips its KV tombstone so the next live host proceeds."""
    kv = KVStore()
    lease = DistributedTicketLease(kv, "ckpt", capacity=1)
    lease.acquire()
    with pytest.raises(TimeoutError):
        lease.acquire(timeout=0.15)  # dying host: tombstoned, not wedged
    got = []
    live = threading.Thread(target=lambda: got.append(lease.acquire(timeout=5.0)))
    live.start()
    time.sleep(0.05)
    lease.release()  # skips the dead ticket
    live.join(5)
    assert got and lease.dead_skipped == 1
    lease.release()
    assert lease.queue_depth() == 0
