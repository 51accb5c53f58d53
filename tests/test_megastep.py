"""Device-resident decode megastep (serving/engine_state + scheduler
.megastep) vs the host-loop oracle, plus the PR-3 satellites:

  * property: K-round ``megastep(K)`` emits the SAME token streams,
    admission rounds/order, and expiry set as K sequential ``step()``
    calls under identical arrivals/deadlines/tenant mixes — including
    per-tenant ticket sequences wrapping 2³²;
  * deadline-aware decode preemption on BOTH paths: an expired running
    sequence is tombstoned, its slot reclaimed and re-granted to the next
    live ticket in FCFS order;
  * `kernels.qos_admission.qos_round_scan` (batch-of-rounds entry) ==
    K sequential `functional_qos.qos_round` calls, bit-exact;
  * compile-cache hits: the power-of-two backlog padding in
    `kernels.ops.qos_round` keeps steady-state serving on ONE compiled
    executable across distinct backlog lengths;
  * telemetry: ``queue_depth`` reflects the live QoS backlog (regression:
    it read the unused global semaphore and reported 0 while thousands
    queued).
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

from repro.serving.engine_state import rid_token_fn
from repro.serving.scheduler import ContinuousBatchingEngine, Request

WEIGHTS = {"gold": 4.0, "silver": 2.0, "bronze": 1.0}
DT = 0.25  # virtual-time grid: exact in float32, so host f64 and in-graph
#            f32 deadline comparisons can never disagree at the boundary


def _rid_step_fn(active):
    """Host-loop counterpart of `engine_state.rid_token_fn`: logits ARE the
    deterministic request-identity token (sampled by identity)."""
    return np.array([r.rid * 1000 + len(r.out_tokens) for r in active],
                    np.int64)


_IDENT = lambda lg: lg.astype(np.int64)  # noqa: E731


def _mk_engine(clk, *, use_kernel=True, n_slots=4, weights=WEIGHTS,
               wrap=False):
    eng = ContinuousBatchingEngine(
        _rid_step_fn, lambda r: None, n_slots, tenants=dict(weights),
        use_kernel=use_kernel, clock=lambda: clk[0])
    if wrap:  # per-tenant ticket sequences straddle 2³² during the run
        base = jnp.uint32((1 << 32) - 7)
        S = len(weights)
        eng.qos = eng.qos._replace(
            ticket=jnp.full((S,), base), grant=jnp.full((S,), base),
            consumed=jnp.full((S,), base))
    return eng


def _workload(seed: int, n_req: int, deadline_frac: float):
    rng = np.random.default_rng(seed)
    names = list(WEIGHTS)
    reqs = []
    for i in range(n_req):
        dl = None
        if rng.random() < deadline_frac:
            dl = DT * int(rng.integers(0, 16))  # on the f32-exact grid
        reqs.append(Request(
            rid=i, prompt=[1 + int(rng.integers(0, 9))],
            max_new_tokens=1 + int(rng.integers(0, 3)),
            tenant_id=names[int(rng.integers(0, len(names)))],
            deadline=dl))
    return reqs


def _compare_engines(seed, deadline_frac, wrap, K=12, n_req=18):
    """Drive identical workloads through the host step-loop and ONE
    megastep(K); every observable must match round-for-round."""
    clk = [0.0]
    eh = _mk_engine(clk, wrap=wrap)
    em = _mk_engine(clk, wrap=wrap)
    rh = _workload(seed, n_req, deadline_frac)
    rm = _workload(seed, n_req, deadline_frac)
    clk[0] = 0.0
    eh.submit_batch(rh)
    em.submit_batch(rm)

    times = [k * DT for k in range(K)]
    for t in times:  # host loop: K syncs at virtual times t_k
        clk[0] = t
        eh.step(_IDENT)
    clk[0] = 0.0  # megastep launches at the epoch; nows carry the times
    em.megastep(K, token_fn=rid_token_fn, nows=np.asarray(times, np.float32))

    for a, b in zip(rh, rm):
        tag = f"seed={seed} rid={a.rid}"
        assert a.out_tokens == b.out_tokens, (tag, a.out_tokens, b.out_tokens)
        assert a.admit_round == b.admit_round, (tag, a.admit_round,
                                                b.admit_round)
        assert a.expired == b.expired, tag
        assert a.preempted == b.preempted, tag
        assert a.expire_round == b.expire_round, (tag, a.expire_round,
                                                  b.expire_round)
    assert eh.stats.finished == em.stats.finished
    assert eh.stats.expired == em.stats.expired
    assert eh.stats.preempted == em.stats.preempted
    assert eh.stats.admitted == em.stats.admitted
    # the QoS semaphore state itself must evolve bit-identically
    for f in eh.qos._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(eh.qos, f)), np.asarray(getattr(em.qos, f)),
            err_msg=f"seed={seed}:{f}")
    assert eh._qos_free == em._qos_free
    # K host syncs collapsed to one launch+drain
    assert eh.stats.host_syncs == K and em.stats.host_syncs == 1


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**32 - 1),            # workload seed
       st.sampled_from([0.0, 0.4, 0.8]),     # deadline density
       st.booleans())                        # tickets wrap 2³²
def test_megastep_equals_host_loop_property(seed, deadline_frac, wrap):
    """ISSUE acceptance: megastep(K) ≡ K sequential step() calls — token
    streams, admission rounds, expiry/preemption sets, the QoS state, and
    the free pool, bit-for-bit, with and without 2³² ticket wrap."""
    _compare_engines(seed, deadline_frac, wrap)


def test_megastep_multi_launch_continuity():
    """Sequences spanning several megasteps (max_new > K): slot state is
    rebuilt from host bookkeeping each launch and streams stay identical
    to the host loop."""
    clk = [0.0]
    eh = _mk_engine(clk, n_slots=2, weights={"a": 1.0})
    em = _mk_engine(clk, n_slots=2, weights={"a": 1.0})
    rh = [Request(rid=i, prompt=[1], max_new_tokens=7, tenant_id="a")
          for i in range(5)]
    rm = [Request(rid=i, prompt=[1], max_new_tokens=7, tenant_id="a")
          for i in range(5)]
    eh.submit_batch(rh)
    em.submit_batch(rm)
    for _ in range(21):
        eh.step(_IDENT)
    for _ in range(7):  # 3 launches of K=7 ≡ 21 steps
        em.megastep(3, token_fn=rid_token_fn)
    for a, b in zip(rh, rm):
        assert a.out_tokens == b.out_tokens, (a.rid, a.out_tokens,
                                              b.out_tokens)
        assert a.admit_round == b.admit_round, a.rid
    assert eh.stats.finished == em.stats.finished == 5
    assert em.stats.host_syncs == 7


# ------------------------------------------------- decode preemption --------


def _preempt_engine(clk, mode):
    return ContinuousBatchingEngine(
        _rid_step_fn, lambda r: None, n_slots=1, tenants={"a": 1.0},
        use_kernel=(mode == "kernel"), clock=lambda: clk[0])


def _drive(eng, mode, clk):
    if mode == "mega":
        eng.megastep(1, token_fn=rid_token_fn, nows=[clk[0]])
    else:
        eng.step(_IDENT)


def _preemption_scenario(mode):
    """A running hog whose deadline passes mid-decode is tombstoned; its
    slot is re-granted to the next live ticket in FCFS order (the earliest
    waiter, not a later one)."""
    clk = [0.0]
    eng = _preempt_engine(clk, mode)
    hog = Request(rid=0, prompt=[1], max_new_tokens=100, tenant_id="a",
                  deadline=2.0)
    nxt = Request(rid=1, prompt=[1], max_new_tokens=2, tenant_id="a")
    later = Request(rid=2, prompt=[1], max_new_tokens=2, tenant_id="a")
    eng.submit_batch([hog, nxt, later])
    for _ in range(3):
        _drive(eng, mode, clk)
        clk[0] += DT
    assert hog.slot == 0 and len(hog.out_tokens) == 3 and not hog.expired
    clk[0] = 2.5  # hog's deadline passes while it is DECODING
    for _ in range(4):
        _drive(eng, mode, clk)
        clk[0] += DT
    assert hog.preempted and hog.expired and hog.done_event.is_set()
    assert len(hog.out_tokens) == 3  # no tokens after preemption
    assert eng.stats.preempted == 1 and eng.stats.expired == 1
    # FCFS re-grant: the freed slot went to `nxt` (earlier ticket), and
    # only after nxt finished could `later` run
    assert nxt.out_tokens == [1000, 1001]
    assert nxt.admit_round < later.admit_round or later.admit_round == -1
    assert eng.tenant_expired["a"] == 1


def test_preempted_slot_regranted_fcfs_host():
    """Satellite: host (non-kernel) step() path."""
    _preemption_scenario("host")


def test_preempted_slot_regranted_fcfs_kernel():
    _preemption_scenario("kernel")


def test_preempted_slot_regranted_fcfs_megastep():
    _preemption_scenario("mega")


def test_preemption_within_single_megastep():
    """The in-graph case: deadline passes at round k INSIDE one megastep —
    the slot is reclaimed mid-scan and the next ticket admitted without
    any host sync."""
    clk = [0.0]
    eng = _preempt_engine(clk, "mega")
    hog = Request(rid=0, prompt=[1], max_new_tokens=100, tenant_id="a",
                  deadline=1.0)
    nxt = Request(rid=1, prompt=[1], max_new_tokens=3, tenant_id="a")
    eng.submit_batch([hog, nxt])
    nows = np.asarray([0.0, 0.5, 1.0, 1.25, 1.5, 1.75], np.float32)
    eng.megastep(6, token_fn=rid_token_fn, nows=nows)
    assert hog.preempted and len(hog.out_tokens) == 2  # rounds 0, 1
    assert nxt.out_tokens == [1000, 1001, 1002]  # admitted at round 2
    assert hog.expire_round == 2 and nxt.admit_round == 2
    assert eng.stats.host_syncs == 1


def test_megastep_drains_deadline_heap():
    """Regression: a non-kernel QoS engine served exclusively via megastep
    must not retain resolved deadline Requests in the host expiry heap
    forever (only the host step() path pops it)."""
    clk = [0.0]
    eng = ContinuousBatchingEngine(
        _rid_step_fn, lambda r: None, n_slots=4, tenants={"a": 1.0},
        use_kernel=False, clock=lambda: clk[0])
    reqs = [Request(rid=i, prompt=[1], max_new_tokens=2, tenant_id="a",
                    deadline=100.0) for i in range(50)]
    eng.submit_batch(reqs)
    assert len(eng._deadline_heap) == 50
    while eng.stats.finished < 50:
        eng.megastep(4, token_fn=rid_token_fn)
    assert eng.stats.finished == 50
    assert len(eng._deadline_heap) == 0


# ------------------------------------------------ batch-of-rounds scan ------


def test_qos_round_scan_matches_sequential_ref():
    """`kernels.qos_admission.qos_round_scan` (K fused rounds under one
    lax.scan, slot-release feedback folded per round) is bit-identical to
    K sequential functional rounds (`ref.qos_round_scan_ref`)."""
    from repro.admission.functional_qos import make_qos, qos_take
    from repro.kernels.qos_admission import qos_round_scan
    from repro.kernels.ref import qos_round_scan_ref

    S, N, K = 3, 24, 3
    rng = np.random.default_rng(11)
    state = make_qos([3.0, 2.0, 1.0], table_size=64)
    ids = jnp.asarray(rng.integers(0, S, N), jnp.int32)
    state, tks, _, _ = qos_take(state, ids, jnp.ones(N, bool))
    alive = jnp.asarray(rng.random(N) > 0.2)
    dls = jnp.asarray(np.where(rng.random(N) > 0.5, rng.uniform(0, 2, N),
                               np.inf), jnp.float32)
    nows = np.asarray([0.0, 0.8, 1.6], np.float32)
    rel = np.asarray([0, 2, 1], np.int32)

    ref = qos_round_scan_ref(state, ids, tks, alive, dls, nows, 4, rel, 8)
    st2, ar, er, fr = qos_round_scan(state, ids, tks, alive, dls,
                                     jnp.asarray(nows), 4, jnp.asarray(rel),
                                     max_units=8, block_n=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(ar),
                                  np.asarray(ref["admit_round"]))
    np.testing.assert_array_equal(np.asarray(er),
                                  np.asarray(ref["expire_round"]))
    assert int(fr) == int(ref["free"])
    for f in state._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(st2, f)),
            np.asarray(getattr(ref["state"], f)), err_msg=f)


# ----------------------------------------------- compile-cache (pow2) -------


def test_qos_round_compile_cache_hits():
    """Satellite: the power-of-two backlog padding keeps every backlog
    length ≤ block_n on ONE compiled executable (the steady-state serving
    case), and a draining multi-block backlog on O(log N) shapes — no
    retrace per distinct length."""
    from repro.admission.functional_qos import make_qos, qos_take
    from repro.kernels import ops
    from repro.kernels.qos_admission import qos_round_fused

    def round_n(n):
        st = make_qos([1.0, 2.0], table_size=64)
        ii = np.zeros(n, np.int32)
        st, tt, _, _ = qos_take(st, jnp.asarray(ii), jnp.ones(n, bool))
        st2, adm, exp, _ = ops.qos_round(
            st, ii, np.asarray(tt), np.ones(n, bool),
            np.full(n, np.inf, np.float32), 0.0, 2, max_units=4)
        assert adm.shape == (n,) and exp.shape == (n,)

    round_n(5)  # warm the steady-state executable
    before = qos_round_fused._cache_size()
    for n in (1, 7, 33, 100, 255, 256):  # all ≤ default block_n=256
        round_n(n)
    assert qos_round_fused._cache_size() == before, \
        "steady-state backlog lengths must share one compiled executable"
    for n in (257, 300, 511, 513, 700, 1000):  # multi-block: pow2 buckets
        round_n(n)
    grown = qos_round_fused._cache_size() - before
    assert grown <= 2, f"expected ≤2 pow2 shapes (512, 1024), got {grown}"


# ------------------------------------------------------- telemetry ----------


def test_telemetry_queue_depth_qos():
    """Satellite regression: in QoS mode ``queue_depth`` must report the
    live per-tenant backlog, not the unused global semaphore (which reads
    0 while thousands queue)."""
    eng = ContinuousBatchingEngine(
        _rid_step_fn, lambda r: None, n_slots=2, tenants={"a": 1.0, "b": 2.0})
    reqs = [Request(rid=i, prompt=[1], max_new_tokens=1,
                    tenant_id=("a", "b")[i % 2]) for i in range(40)]
    eng.submit_batch(reqs)
    tel = eng.telemetry()
    assert tel["queue_depth"] == tel["backlog"] == 40
    while eng.stats.finished < 40:
        eng.step(lambda lg: np.zeros(len(lg), np.int64))
    assert eng.telemetry()["queue_depth"] == 0
