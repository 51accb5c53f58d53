"""Fused QoS admission kernel (kernels/qos_admission) vs the functional
oracle, plus the PR-2 reference-path invariants:

  * kernel == `qos_round` bit-exactly (interpret mode) across random tenant
    mixes, ticket wrap-around near 2³², all-dead batches, and
    zero-weight/zero-free edge cases — every state field, both row masks,
    and the leftover unit count;
  * blocked-prefix `live_fifo_rank` == the retained O(N²) pairwise oracle;
  * the replenish poke window decays with reclaim (dead-below-frontier)
    instead of growing monotonically with total expirations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

try:  # hypothesis is an optional test dependency (pyproject `test` extra)
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, strategies as st

from repro.admission.functional_qos import (
    QoSState,
    make_qos,
    qos_reclaim,
    qos_replenish,
    qos_round,
    qos_take,
)
from repro.core.functional import live_fifo_rank, live_fifo_rank_pairwise
from repro.kernels.qos_admission import qos_round_fused


def _assert_round_equal(state, ids, tickets, alive, dls, now, free, mu,
                        block_n, tag=""):
    ref = qos_round(state, ids, tickets, alive, dls, now, free, mu)
    ker = qos_round_fused(state, ids, tickets, alive, dls, now, free,
                          max_units=mu, block_n=block_n, interpret=True)
    rs, ra, re, rl = ref
    ks, ka, ke, kl = ker
    np.testing.assert_array_equal(np.asarray(ra), np.asarray(ka), err_msg=tag)
    np.testing.assert_array_equal(np.asarray(re), np.asarray(ke), err_msg=tag)
    for f in QoSState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(rs, f)), np.asarray(getattr(ks, f)),
            err_msg=f"{tag}:{f}")
    assert int(rl) == int(kl), (tag, int(rl), int(kl))


def _random_round(seed: int, alive_density: float, expire_density: float,
                  free: int, wrap: bool):
    """Fixed shapes (one compiled kernel), random data: weights (incl. 0),
    tenant mix, per-tenant consecutive tickets (optionally wrapping 2³²),
    alive mask, deadlines."""
    S, N, TBL, MU = 4, 32, 128, 16
    rng = np.random.default_rng(seed)
    state = make_qos(rng.integers(0, 5, S).astype(np.float32), table_size=TBL)
    base = np.uint32((1 << 32) - 13) if wrap else np.uint32(0)
    state = state._replace(
        ticket=jnp.full((S,), base, jnp.uint32),
        grant=jnp.full((S,), base, jnp.uint32),
        consumed=jnp.full((S,), base, jnp.uint32),
        dead=jnp.asarray(rng.integers(0, 3, S), jnp.uint32),
        vpass=jnp.asarray(rng.uniform(0, 2, S), jnp.float32))
    ids = jnp.asarray(rng.integers(0, S, N), jnp.int32)
    state, tickets, _, _ = qos_take(state, ids, jnp.ones(N, bool))
    alive = jnp.asarray(rng.random(N) < alive_density)
    dls = jnp.asarray(np.where(rng.random(N) < expire_density,
                               rng.uniform(-1, 1, N), np.inf), jnp.float32)
    return state, ids, tickets, alive, dls, free, MU


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2**32 - 1),   # seed
       st.sampled_from([0.0, 0.3, 0.8, 1.0]),   # alive density (0 = all dead)
       st.sampled_from([0.0, 0.4, 1.0]),        # expire density
       st.integers(0, 20),          # free units
       st.booleans())               # tickets wrap 2³²
def test_qos_kernel_matches_oracle_property(seed, dens, exp, free, wrap):
    state, ids, tickets, alive, dls, free, mu = _random_round(
        seed, dens, exp, free, wrap)
    _assert_round_equal(state, ids, tickets, alive, dls, 0.0, free, mu,
                        block_n=16, tag=f"seed={seed}")


def test_qos_kernel_all_dead_batch():
    state, ids, tickets, _, dls, _, mu = _random_round(3, 1.0, 0.0, 7, False)
    _assert_round_equal(state, ids, tickets, jnp.zeros(32, bool), dls,
                        0.0, 7, mu, block_n=16, tag="all-dead")


def test_qos_kernel_zero_weight_free_units():
    """Zero-weight tenants: at most one unit (their first crossing), then
    their virtual pass saturates to +inf — kernel and oracle agree."""
    state = make_qos([0.0, 0.0, 2.0], table_size=64)
    ids = jnp.asarray([0] * 4 + [1] * 4 + [2] * 4, jnp.int32)
    state, tickets, _, _ = qos_take(state, ids, jnp.ones(12, bool))
    dls = jnp.full((12,), np.inf, jnp.float32)
    _assert_round_equal(state, ids, tickets, jnp.ones(12, bool), dls,
                        0.0, 10, 8, block_n=8, tag="zero-weight")
    # and the round after (vpass now inf for any granted zero-weight tenant)
    s2, admitted, _, _ = qos_round(state, ids, tickets, jnp.ones(12, bool),
                                   dls, 0.0, 10, 8)
    _assert_round_equal(s2, ids, tickets, jnp.ones(12, bool) & ~admitted,
                        dls, 0.0, 4, 8, block_n=8, tag="zero-weight-2")


def test_qos_kernel_ticket_wraparound_multiblock():
    """Per-tenant ticket sequences spanning the 2³² wrap, shuffled row
    order, N spanning several kernel blocks."""
    S, N = 3, 200
    rng = np.random.default_rng(11)
    state = make_qos([4.0, 2.0, 1.0], table_size=256)
    base = np.uint32((1 << 32) - 60)
    state = state._replace(ticket=jnp.full((S,), base, jnp.uint32),
                           grant=jnp.full((S,), base, jnp.uint32),
                           consumed=jnp.full((S,), base, jnp.uint32))
    ids = jnp.asarray(rng.integers(0, S, N), jnp.int32)
    state, tickets, _, _ = qos_take(state, ids, jnp.ones(N, bool))
    perm = rng.permutation(N)
    alive = jnp.asarray(rng.random(N) > 0.3)
    dls = jnp.asarray(np.where(rng.random(N) > 0.5,
                               rng.uniform(0, 2, N), np.inf), jnp.float32)
    _assert_round_equal(state, ids[perm], tickets[perm], alive[perm],
                        dls[perm], 1.0, 9, 12, block_n=64, tag="wrap")


def test_qos_round_empty_backlog():
    """N=0 backlog: reference, blocked rank, and padded kernel wrapper all
    return empty masks and conserve the free units (regression: the
    ticket-order argsort used to gather from an empty array)."""
    from repro.kernels.ops import qos_round as qos_round_ops

    s = make_qos([1.0, 2.0], table_size=64)
    empty_i = jnp.zeros((0,), jnp.int32)
    _, admitted, expired, leftover = qos_round(
        s, empty_i, jnp.zeros((0,), jnp.uint32), jnp.zeros((0,), bool),
        jnp.zeros((0,), jnp.float32), 0.0, 3, 4)
    assert admitted.shape == (0,) and expired.shape == (0,)
    assert int(leftover) == 3
    assert live_fifo_rank(empty_i, jnp.zeros((0,), jnp.uint32),
                          jnp.zeros((0,), bool), 2).shape == (0,)
    _, ka, ke, kl = qos_round_ops(
        s, np.zeros(0, np.int32), np.zeros(0, np.uint32), np.zeros(0, bool),
        np.zeros(0, np.float32), 0.0, 3, max_units=4)
    assert ka.shape == (0,) and ke.shape == (0,) and int(kl) == 3


# ------------------------------------------------- blocked-prefix rank ------


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**32 - 1), st.booleans())
def test_live_fifo_rank_blocked_equals_pairwise(seed, wrap):
    """The O(N·S/block) blocked-prefix rank == the retained O(N²) pairwise
    oracle, for shuffled per-tenant-unique tickets with and without 2³²
    wrap-around, under arbitrary alive masks."""
    rng = np.random.default_rng(seed)
    S, N = 5, 97
    ids = rng.integers(0, S, N).astype(np.int32)
    base = np.uint32((1 << 32) - 40) if wrap else np.uint32(rng.integers(0, 1000))
    tickets = np.zeros(N, np.uint32)
    counters = np.full(S, base, np.uint32)
    for r in range(N):  # per-tenant consecutive (the take-time invariant)
        tickets[r] = counters[ids[r]]
        counters[ids[r]] += np.uint32(1)
    perm = rng.permutation(N)
    ids, tickets = ids[perm], tickets[perm]
    alive = rng.random(N) > 0.25
    got = live_fifo_rank(jnp.asarray(ids), jnp.asarray(tickets),
                         jnp.asarray(alive), S, block=32)
    want = live_fifo_rank_pairwise(jnp.asarray(ids), jnp.asarray(tickets),
                                   jnp.asarray(alive))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_live_fifo_rank_blocked_flops_under_a_tenth_of_pairwise():
    """At N=4096 rows over S=8 tenants the blocked-prefix rank compiles to
    under a tenth of the pairwise rank's FLOPs (XLA's cost analysis): the
    O(N·S/block) path wins by its asymptotics, not by a constant."""
    N, S = 4096, 8
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, S, N), jnp.int32)
    tickets = jnp.arange(N, dtype=jnp.uint32)
    alive = jnp.asarray(rng.random(N) > 0.2)

    def flops(fn):
        return jax.jit(fn).lower(ids, tickets, alive).compile() \
            .cost_analysis()["flops"]

    blocked = flops(lambda i, t, a: live_fifo_rank(i, t, a, S))
    pairwise = flops(live_fifo_rank_pairwise)
    assert 0 < blocked < pairwise / 10, (blocked, pairwise)


# -------------------------------------------- poke-window decay (dead) ------


def test_poke_window_decays_with_reclaim():
    """Regression (ROADMAP open item): the conservative replenish poke
    window must NOT grow monotonically with total expirations.  Credit
    granted to demand that then dies is reclaimed, and each reclaimed unit
    absorbs one tombstone's worth of window slack — so repeated
    grant→expire→reclaim cycles keep `dead` bounded by the per-cycle death
    count instead of accumulating 2 per cycle."""
    s = make_qos([1.0], table_size=64)
    deads = []
    for cycle in range(6):
        ids = jnp.zeros((2,), jnp.int32)
        s, tk, _, _ = qos_take(s, ids, jnp.ones(2, bool))
        # grant 2 units to the live demand…
        s, alloc, _ = qos_replenish(s, 2, jnp.asarray([2], jnp.int32),
                                    max_units=4)
        assert int(alloc[0]) == 2
        # …then both waiters die before admission: stranded credit
        s = s._replace(dead=s.dead + jnp.uint32(2))
        s, reclaimed = qos_reclaim(s, jnp.asarray([0], jnp.int32))
        assert int(reclaimed) == 2
        deads.append(int(s.dead[0]))
    assert max(deads) == 0  # fully absorbed every cycle (old: 2·(cycle+1))


def test_poke_window_partial_reclaim_keeps_slack():
    """Unreclaimed tombstones keep their (sound) window slack: only the
    absorbed portion decays."""
    s = make_qos([1.0], table_size=64)
    ids = jnp.zeros((3,), jnp.int32)
    s, tk, _, _ = qos_take(s, ids, jnp.ones(3, bool))
    s, alloc, _ = qos_replenish(s, 1, jnp.asarray([3], jnp.int32), max_units=4)
    s = s._replace(dead=s.dead + jnp.uint32(2))  # two die, one unit stranded?
    # live depth 1 (one waiter left), avail 1 → nothing stranded yet
    s, reclaimed = qos_reclaim(s, jnp.asarray([1], jnp.int32))
    assert int(reclaimed) == 0 and int(s.dead[0]) == 2
    # the last waiter dies too → the unit strands → one tombstone absorbed
    s, reclaimed = qos_reclaim(s, jnp.asarray([0], jnp.int32))
    assert int(reclaimed) == 1 and int(s.dead[0]) == 1


# ------------------------------------------------------ engine (kernel) -----


def test_engine_qos_kernel_path():
    """ContinuousBatchingEngine(use_kernel=True, tenants=…): the fused
    kernel round drives admission — all requests finish, deadline expiry is
    tombstoned, FCFS per tenant holds (admit order == ticket order)."""
    import time

    from repro.serving.scheduler import ContinuousBatchingEngine, Request

    weights = {"a": 2.0, "b": 1.0}
    eng = ContinuousBatchingEngine(
        lambda active: np.zeros(len(active)), lambda r: None, n_slots=3,
        tenants=weights, use_kernel=True)
    reqs, rid = [], 0
    for _ in range(10):
        for t in weights:
            reqs.append(Request(rid=rid, prompt=[1], max_new_tokens=1,
                                tenant_id=t))
            rid += 1
    doa = Request(rid=rid, prompt=[1], max_new_tokens=1, tenant_id="a",
                  deadline=time.monotonic() - 1.0)
    eng.submit_batch(reqs + [doa])
    steps = 0
    while eng.stats.finished + eng.stats.expired < len(reqs) + 1 and steps < 200:
        eng.step(lambda lg: np.zeros(len(lg), np.int64))
        steps += 1
    assert eng.stats.finished == len(reqs)
    assert doa.expired and doa.done_event.is_set()
    assert eng.stats.expired == 1
    for t in weights:
        admitted = [r for r in reqs if r.tenant_id == t and r.admit_t > 0]
        tks = [r.ticket for r in sorted(admitted, key=lambda r: r.admit_t)]
        assert tks == sorted(tks), t
