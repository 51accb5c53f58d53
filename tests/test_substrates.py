"""Substrate tests: data pipeline, checkpointing, coordinator, elastic
re-sharding, serving scheduler, gradient compression."""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import BoundedBuffer, DataLoader, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.optim.compression import BLOCK, compress_psum, init_residuals
from repro.runtime.coordinator import Coordinator, DistributedTicketLease, KVStore
from repro.serving.scheduler import ContinuousBatchingEngine, Request


# ------------------------------------------------------------------ data ----


def test_synthetic_deterministic():
    src = SyntheticLM(vocab=512, seq_len=64, seed=3)
    a = src.sample(42)
    b = src.sample(42)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # shifted-by-one labels
    np.testing.assert_array_equal(a["tokens"][1:], a["labels"][:-1])


def test_bounded_buffer_fifo_and_backpressure():
    buf = BoundedBuffer(depth=4)
    for i in range(4):
        buf.put(i)
    bp = buf.backpressure()
    assert bp["items_ready"] == 4
    got = [buf.get() for _ in range(4)]
    assert got == [0, 1, 2, 3]  # FIFO through the TWA semaphores

    # producer blocks at depth, unblocks on get
    buf2 = BoundedBuffer(depth=1)
    buf2.put("a")
    t = threading.Thread(target=buf2.put, args=("b",))
    t.start()
    time.sleep(0.05)
    assert t.is_alive()  # blocked on `free`
    assert buf2.get() == "a"
    t.join(timeout=10)
    assert buf2.get() == "b"


def test_loader_resume_determinism():
    """Same start_step ⇒ same batches regardless of worker count (FIFO
    buffer + deterministic per-index sampling)."""
    src = SyntheticLM(vocab=128, seq_len=16, seed=1)

    def first_batches(n_workers, start_step, n=3):
        dl = DataLoader(src, 4, n_workers=n_workers, depth=2, start_step=start_step)
        it = iter(dl)
        out = [next(it)["tokens"].copy() for _ in range(n)]
        dl.stop()
        return out

    a = first_batches(1, 5)
    b = first_batches(3, 5)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_loader_host_sharding_disjoint():
    src = SyntheticLM(vocab=128, seq_len=16, seed=1)
    dl0 = DataLoader(src, 2, n_workers=1, host_id=0, n_hosts=2)
    dl1 = DataLoader(src, 2, n_workers=1, host_id=1, n_hosts=2)
    b0 = next(iter(dl0))["tokens"]
    b1 = next(iter(dl1))["tokens"]
    dl0.stop(), dl1.stop()
    assert not np.array_equal(b0, b1)


# ------------------------------------------------------------ checkpoint ----


def test_checkpoint_roundtrip_and_atomicity(tmp_path):
    ck = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)},
            "lst": [jnp.zeros((2,)), jnp.full((3,), 7.0)]}
    ck.save(3, tree, blocking=True)
    ck.save(7, jax.tree.map(lambda x: x + 1, tree), blocking=True)
    assert ck.complete_steps() == [3, 7]
    restored, step = ck.restore(tree)
    assert step == 7
    np.testing.assert_allclose(np.asarray(restored["a"]), np.asarray(tree["a"]) + 1)
    assert restored["b"]["c"].dtype == jnp.bfloat16
    # torn checkpoint (tmp dir) is invisible
    (tmp_path / "step_000000099.tmp").mkdir()
    assert ck.latest_step() == 7


def test_checkpoint_gc_keeps_newest(tmp_path):
    ck = CheckpointManager(str(tmp_path), keep=2)
    tree = {"x": jnp.ones((2,))}
    for s in (1, 2, 3, 4):
        ck.save(s, tree, blocking=True)
    assert ck.complete_steps() == [3, 4]


def test_checkpoint_multihost_commit(tmp_path):
    """Finalize waits for every host's commit marker (simulated hosts)."""
    tree = {"x": jnp.ones((2,))}
    h0 = CheckpointManager(str(tmp_path), host_id=0, expected_hosts=2)
    h1 = CheckpointManager(str(tmp_path), host_id=1, expected_hosts=2)
    t = threading.Thread(target=h0.save, args=(5, tree), kwargs={"blocking": True})
    t.start()
    time.sleep(0.1)
    assert h0.complete_steps() == []  # host 1 not committed yet
    h1.save(5, tree, blocking=True)
    t.join(timeout=30)
    assert h0.complete_steps() == [5]


def test_emergency_sync_save(tmp_path):
    ck = CheckpointManager(str(tmp_path))
    ck.save_sync(11, {"x": jnp.ones((3,))})
    assert ck.latest_step() == 11


# ------------------------------------------------------------ coordinator ---


def test_lease_fifo_and_queue_depth():
    kv = KVStore()
    lease = DistributedTicketLease(kv, "ckpt", capacity=1)
    order = []

    def worker(i):
        lease.acquire()
        order.append(i)
        time.sleep(0.01)
        lease.release()

    ts = []
    for i in range(4):
        t = threading.Thread(target=worker, args=(i,))
        t.start()
        time.sleep(0.02)  # serialize ticket issuance
        ts.append(t)
    for t in ts:
        t.join(timeout=30)
    assert order == [0, 1, 2, 3]  # FCFS across "hosts"
    assert lease.queue_depth() == 0


def test_failure_detection_and_barrier():
    c = Coordinator(heartbeat_timeout=0.2)
    for h in (0, 1, 2):
        c.join(h)
    c.heartbeat(0, 1, 0.1)
    c.heartbeat(1, 1, 0.1)
    c.heartbeat(2, 1, 0.1)
    assert c.detect_failures() == []
    time.sleep(0.3)
    c.heartbeat(0, 2, 0.1)
    c.heartbeat(1, 2, 0.1)  # host 2 silent
    dead = c.detect_failures()
    assert dead == [2]
    assert c.alive_hosts() == [0, 1]
    # failure-aware barrier completes with survivors only
    done = []
    t0 = threading.Thread(target=lambda: done.append(c.barrier(0, "g1")))
    t1 = threading.Thread(target=lambda: done.append(c.barrier(1, "g1")))
    t0.start(), t1.start()
    t0.join(timeout=15), t1.join(timeout=15)
    assert done == [True, True]


def test_straggler_detection():
    c = Coordinator()
    for h in range(4):
        c.join(h)
    for _ in range(5):
        for h in range(4):
            c.heartbeat(h, 1, 0.1 if h != 3 else 0.5)
    assert c.stragglers() == [3]


# ----------------------------------------------------------- compression ----


def test_compression_ef_residual_correctness():
    """Single-shard compress_psum must reconstruct g up to block quantization,
    and the residual must carry exactly the quantization error."""
    import jax

    mesh = make_mesh((1,), ("pod",))
    g = jnp.asarray(np.random.default_rng(0).normal(size=(1000,)), jnp.float32)
    r0 = jnp.zeros_like(g)

    def f(g, r):
        return compress_psum(g, r, "pod", 1)

    out, res = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=(jax.sharding.PartitionSpec(),) * 2,
                         out_specs=(jax.sharding.PartitionSpec(),) * 2)
    )(g, r0)
    np.testing.assert_allclose(np.asarray(out + res), np.asarray(g), atol=1e-5)
    # quantization error bounded by scale = blockmax/127
    blocks = np.abs(np.asarray(g)).reshape(-1, BLOCK) if g.size % BLOCK == 0 else None
    assert float(jnp.max(jnp.abs(res))) <= float(jnp.max(jnp.abs(g))) / 127.0 + 1e-6


def test_compression_unbiased_over_time():
    """Error feedback: Σ_t compressed_t ≈ Σ_t g_t (noise does not accumulate)."""
    rng = np.random.default_rng(1)
    mesh = make_mesh((1,), ("pod",))
    P = jax.sharding.PartitionSpec

    @jax.jit
    def step(g, r):
        return jax.shard_map(lambda g, r: compress_psum(g, r, "pod", 1),
                                mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))(g, r)

    r = jnp.zeros((512,), jnp.float32)
    total_true = np.zeros(512)
    total_comp = np.zeros(512)
    for t in range(30):
        g = jnp.asarray(rng.normal(size=(512,)) * (1 + t % 3), jnp.float32)
        out, r = step(g, r)
        total_true += np.asarray(g)
        total_comp += np.asarray(out)
    # cumulative drift bounded by one quantization step, not 30
    assert np.max(np.abs(total_true - total_comp)) < np.abs(total_true).max() * 0.02 + 0.1


# ---------------------------------------------------------------- serving ---


def _toy_engine(n_slots=2, use_kernel=False):
    """Engine over a fake model: next token = len(out_tokens)."""

    def step_fn(active_reqs):
        return np.arange(len(active_reqs))

    def prefill_fn(req):
        pass

    return ContinuousBatchingEngine(step_fn, prefill_fn, n_slots, use_kernel=use_kernel)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_engine_fcfs_admission(use_kernel):
    eng = _toy_engine(n_slots=2, use_kernel=use_kernel)
    reqs = [Request(rid=i, prompt=[1], max_new_tokens=2) for i in range(6)]
    eng.submit_batch(reqs)
    admit_order = []
    for _ in range(20):
        eng.step(lambda lg: lg[:, None].argmax(1) if hasattr(lg, "ndim") else lg)
        for slot, r in eng.active.items():
            if r.rid not in admit_order:
                admit_order.append(r.rid)
        if eng.stats.finished == 6:
            break
    assert eng.stats.finished == 6
    # FCFS: admission order == submission order (tickets are ordered)
    assert admit_order == sorted(admit_order)


def test_engine_backlog_skipping():
    """TWA property: with a deep backlog, un-poked requests are not
    re-examined."""
    eng = _toy_engine(n_slots=2)
    reqs = [Request(rid=i, prompt=[1], max_new_tokens=3) for i in range(40)]
    eng.submit_batch(reqs)
    for _ in range(100):
        eng.step(lambda lg: np.zeros(len(lg), np.int64))
        if eng.stats.finished == 40:
            break
    assert eng.stats.finished == 40
    st = eng.stats
    # the scheduler should have skipped far more backlog entries than it
    # scanned (the anti-global-spinning effect)
    assert st.backlog_skipped > st.backlog_scans
    tel = eng.telemetry()
    assert tel["backlog"] == 0 and tel["active"] == 0
