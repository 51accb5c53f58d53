"""Smoke run of the serving engine on one TPU chip.

    python chip_smoke.py [--seed N]

One process, three phases, each checked; any failure raises and the script
exits non-zero without its final line.

* kernels  — the four served Pallas kernels (`qos_round_fused`,
  `sema_batch`, `paged_decode`, `paged_prefill`) compiled natively and
  compared with their oracles in `repro.kernels.ref` on seeded inputs.
* megastep — the served engine configuration (four weighted tenants, the
  fused admission kernel, the block-paged KV pool, chunked prefill and the
  prefix cache) drains a backlog of 1,024 seeded requests with shared
  system prompts over 64 slots and a 4,096-block pool, driven through the
  recovery ladder.  Every request must finish with its full token count,
  every round's health bitmask must be 0, the ladder must never fall back
  from the kernel, the exit conservation audit must pass, and every
  compiled megastep must contain the kernel (`tpu_custom_call`).  A small
  workload is also drained twice, with the fused kernel and with the
  functional admission path, and the two must agree request for request.
* model    — full-width qwen2-0.5b in bfloat16 served by `launch.serve`'s
  `ModelServer` through `ContinuousBatchingEngine.step`; each request's
  prompt logits from the server's chunked prefill must match the model's
  own `prefill` of the same prompt within a bfloat16 tolerance, and every
  logit must be finite.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TENANTS = {"gold": 4.0, "silver": 2.0, "bronze": 1.0, "free": 1.0}
# the served deployment: a 1,024-request backlog over 64 slots and a
# 4,096-block pool of 16-token blocks, 16-block tables, 32-token prefill
# chunks; `tests/test_tpu_compile.py` compiles the kernels at these widths
N_REQ, N_SLOTS, N_BLOCKS, BLOCK, TABLE, CHUNK = 1024, 64, 4096, 16, 16, 32
N_REF = 64  # requests drained by both admission paths
VOCAB, K = 32000, 32  # the pool model's vocabulary; rounds per megastep
# full-width qwen2-0.5b in bfloat16: 8 requests of 32 prompt tokens and 16
# new ones over 4 slots; served vs prefill relative L2 at most MODEL_TOL
MODEL, M_REQ, M_SLOTS, M_PROMPT, M_NEW = "qwen2-0.5b", 8, 4, 32, 16
MODEL_TOL = 5e-2


class CompileClock:
    """Sums JAX's backend compile durations (persistent-cache hits
    included, which take far less)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1


@contextmanager
def compiled_megastep():
    """Runs `engine_state.megastep_jit` through ahead-of-time compiled
    executables, one per argument signature, and yields the list of their
    HLO texts so the caller can check what ran."""
    from repro.serving import engine_state

    jitted = engine_state.megastep_jit
    compiled, texts = {}, []

    def run(*args, **static):
        key = (jax_signature(args), tuple(sorted(static.items(),
                                                 key=lambda kv: kv[0])))
        if key not in compiled:
            compiled[key] = jitted.lower(*args, **static).compile()
            texts.append(compiled[key].as_text())
        return compiled[key](*args)

    engine_state.megastep_jit = run
    try:
        yield texts
    finally:
        engine_state.megastep_jit = jitted


def jax_signature(tree):
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, tuple((x.shape, str(x.dtype)) for x in leaves)


# ------------------------------------------------------------- kernels ----


def kernels_phase(seed: int) -> dict:
    """Each served kernel, native, against its oracle on seeded inputs."""
    import jax
    import jax.numpy as jnp

    from repro.admission.functional_qos import QoSState, make_qos, qos_take
    from repro.kernels import ref
    from repro.kernels.ops import use_interpret
    from repro.kernels.paged_decode import paged_decode
    from repro.kernels.paged_prefill import paged_prefill
    from repro.kernels.qos_admission import qos_round_fused
    from repro.kernels.sema_batch import sema_batch

    interp = use_interpret()
    rng = np.random.default_rng(seed)
    out = {}

    # admission round: integer semantics, bit-exact
    S, N, MU = len(TENANTS), 1024, 64
    st = make_qos(list(TENANTS.values()), table_size=256)
    ids = jnp.asarray(rng.integers(0, S, N), jnp.int32)
    st, tickets, _, _ = qos_take(st, ids, jnp.ones(N, bool))
    alive = jnp.asarray(rng.random(N) < 0.9)
    dls = jnp.asarray(np.where(rng.random(N) < 0.1, rng.uniform(-1, 1, N),
                               np.inf), jnp.float32)
    want = ref.qos_round_ref(st, ids, tickets, alive, dls, 0.0, 48, MU)
    got = qos_round_fused(st, ids, tickets, alive, dls, 0.0, 48,
                          max_units=MU, interpret=interp)
    for f in QoSState._fields:
        np.testing.assert_array_equal(np.asarray(getattr(want["state"], f)),
                                      np.asarray(getattr(got[0], f)), f)
    np.testing.assert_array_equal(np.asarray(want["admitted"]),
                                  np.asarray(got[1]))
    np.testing.assert_array_equal(np.asarray(want["expired"]),
                                  np.asarray(got[2]))
    assert int(want["leftover"]) == int(got[3])
    out["qos_round_admitted"] = int(np.asarray(got[1]).sum())

    # batched semaphore pass: bit-exact
    req = jnp.asarray(rng.random(N) < 0.7)
    args = (jnp.uint32(5), jnp.uint32(40),
            jnp.asarray(rng.integers(0, 9, 256), jnp.uint32), req,
            jnp.uint32(17), jnp.uint32(0x9E3779B9))
    want = ref.sema_batch_ref(*args)
    got = sema_batch(*args, interpret=interp)
    for k, g in zip(("ticket", "grant", "bucket_seq", "tickets", "admitted",
                     "bucket", "woken"), got):
        np.testing.assert_array_equal(np.asarray(want[k]), np.asarray(g), k)

    # paged attention at qwen2-0.5b head geometry over the served pool
    Sl, H, KV, hd = N_SLOTS, 14, 2, 64
    NB, BS, MB, CT = N_BLOCKS, BLOCK, TABLE, CHUNK
    bf = jnp.bfloat16
    norm = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    kp, vp = norm(NB, BS, KV, hd).astype(bf), norm(NB, BS, KV, hd).astype(bf)
    tbl = jnp.asarray(rng.permutation(NB)[:Sl * MB].reshape(Sl, MB),
                      jnp.int32)
    lens = jnp.asarray(rng.integers(1, MB * BS + 1, Sl), jnp.int32)
    q = norm(Sl, H, hd).astype(bf)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode_ref(q, kp, vp, tbl, lens)
    got = paged_decode(q, kp, vp, tbl, lens, interpret=interp)
    out["paged_decode_max_err"] = _close(got, want, 3e-2)

    off = jnp.asarray(rng.integers(0, MB * BS - CT + 1, Sl), jnp.int32)
    clen = jnp.asarray(rng.integers(0, CT + 1, Sl), jnp.int32)
    qc = norm(Sl, CT, H, hd).astype(bf)
    kc, vc = norm(Sl, CT, KV, hd).astype(bf), norm(Sl, CT, KV, hd).astype(bf)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_prefill_ref(qc, kc, vc, kp, vp, tbl, off, clen)
    got = paged_prefill(qc, kc, vc, kp, vp, tbl, off, clen, interpret=interp)
    out["paged_prefill_max_err"] = _close(got[0], want[0], 3e-2)
    # the pools are written, not computed: exact
    np.testing.assert_array_equal(np.asarray(got[1], np.float32),
                                  np.asarray(want[1], np.float32))
    np.testing.assert_array_equal(np.asarray(got[2], np.float32),
                                  np.asarray(want[2], np.float32))
    return out


def _close(got, want, tol: float) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape and np.isfinite(got).all()
    err = float(np.abs(got - want).max())
    assert err <= tol, f"max |kernel − oracle| = {err} > {tol}"
    return err


# ------------------------------------------------------------ megastep ----


def make_requests(seed: int, n_req: int):
    """Seeded requests: one of 8 shared 64-token system prompts plus a
    private tail of 8 to 47 tokens, asking for 4 to 31 new tokens, spread
    over the tenants."""
    from repro.serving.scheduler import Request

    rng = np.random.default_rng(seed)
    systems = rng.integers(1, VOCAB, (8, 64))
    names = list(TENANTS)
    reqs = []
    for i in range(n_req):
        tail_toks = rng.integers(1, VOCAB, int(rng.integers(8, 48)))
        prompt = np.concatenate([systems[rng.integers(len(systems))],
                                 tail_toks])
        reqs.append(Request(
            rid=i, prompt=[int(t) for t in prompt],
            max_new_tokens=int(rng.integers(4, 32)),
            tenant_id=names[int(rng.integers(len(names)))]))
    return reqs


def drain(reqs, tok_fn, *, use_kernel: bool, admit_impl="auto"):
    """Serve ``reqs`` to the last token through the recovery ladder in the
    served configuration (a 512-token prefill budget per round, 128-token
    prompt cap, 1,024 prefix-cache entries), prefilling
    ``tok_fn._chunk_window`` tokens per chunk; returns (engine, ladder,
    launches)."""
    import jax

    from repro.resilience.recovery import ResilientEngine
    from repro.serving.engine_state import make_paged_pool_model
    from repro.serving.scheduler import ContinuousBatchingEngine

    chunk = tok_fn._chunk_window

    DT = 0.25
    clk = [0.0]
    eng = ContinuousBatchingEngine(
        lambda active: None, lambda r: None, N_SLOTS, tenants=TENANTS,
        use_kernel=use_kernel, clock=lambda: clk[0],
        kv_pool=(N_BLOCKS, BLOCK, TABLE), prompt_cap=128,
        chunked_prefill=(chunk, 512), prefix_cache=1024, watchdog=64)
    eng.megastep_model = make_paged_pool_model(
        jax.random.PRNGKey(0), vocab=VOCAB, d=128, num_blocks=N_BLOCKS,
        block_size=BLOCK)
    ladder = ResilientEngine(eng, react_every=K)
    eng.submit_batch(reqs)
    launches = 0
    while eng.stats.finished < len(reqs):
        if launches == 400:
            raise RuntimeError(f"{eng.stats.finished}/{len(reqs)} finished "
                               f"after {launches} megasteps")
        base = eng._round_no
        nows = np.asarray([(base + k) * DT for k in range(K)], np.float32)
        clk[0] = 0.0
        ladder.megastep(K, token_fn=tok_fn, nows=nows, admit_impl=admit_impl)
        clk[0] = float(nows[-1]) + DT
        launches += 1
    return eng, ladder, launches


def check_drained(eng, ladder, reqs) -> None:
    from repro.resilience.recovery import exit_audit

    short = [r.rid for r in reqs
             if len(r.out_tokens) != r.max_new_tokens or r.expired]
    assert not short, f"requests without their full output: {short[:10]}"
    health = 0
    for smp in ladder.samples:
        health |= int(smp["health"])
    assert health == 0, f"health bitmask {health:#x}"
    assert eng.stats.kernel_fallbacks == 0, "recovery ladder left the kernel"
    assert eng.stats.quarantined == 0 and eng.stats.kv_audits == 0
    audit = exit_audit(eng)
    assert audit["ok"], audit["violations"]


def megastep_phase(seed: int) -> dict:
    """The served engine drains a deployment-sized backlog, and every
    compiled megastep holds the fused admission kernel; a small workload
    is checked against the functional admission path."""
    from repro.serving.engine_state import make_chunked_prefill_token_fn

    tok_fn = make_chunked_prefill_token_fn(CHUNK)  # one closure: one jit key
    with compiled_megastep() as texts:
        reqs = make_requests(seed, N_REQ)
        t0 = time.perf_counter()
        eng, ladder, launches = drain(reqs, tok_fn, use_kernel=True)
        wall = time.perf_counter() - t0
        check_drained(eng, ladder, reqs)
        prefilled = sum(s["prefill_tokens"] for s in ladder.samples)
        prompted = sum(len(r.prompt) for r in reqs)
        # whole prompts never hit (every tail is private), but the shared
        # system prompts must come from the prefix cache, not be prefilled
        assert prefilled < prompted, \
            f"prefix cache unused: {prefilled} of {prompted} prompt tokens"
        served_texts = list(texts)
        assert served_texts and all("tpu_custom_call" in t
                                    for t in served_texts), \
            "a compiled megastep lacks the fused admission kernel"

        small = make_requests(seed + 1, N_REF)
        e1, l1, _ = drain(small, tok_fn, use_kernel=True)
        check_drained(e1, l1, small)
        twin = make_requests(seed + 1, N_REF)
        e2, l2, _ = drain(twin, tok_fn, use_kernel=False, admit_impl=None)
        check_drained(e2, l2, twin)
        for a, b in zip(small, twin):
            assert (a.admit_round, a.slot, a.out_tokens) == \
                (b.admit_round, b.slot, b.out_tokens), \
                f"rid {a.rid}: kernel and functional admission disagree"
    return {
        "requests": N_REQ,
        "tokens": sum(len(r.out_tokens) for r in reqs),
        "rounds": eng._round_no,
        "megasteps": launches,
        "drain_seconds": wall,
        "prompt_tokens": prompted,
        "prefill_tokens": prefilled,
        "prefix_hits": eng.stats.prefix_hits,
        "prefill_chunks": eng.stats.prefill_chunks,
        "programs": len(served_texts),
        "programs_with_kernel": sum("tpu_custom_call" in t
                                    for t in served_texts),
        "reference_requests": N_REF,
    }


# --------------------------------------------------------------- model ----


def model_phase(seed: int) -> dict:
    """``MODEL`` served in bfloat16 by `ModelServer` through the host engine
    step."""
    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_config
    from repro.launch.serve import ModelServer
    from repro.models.transformer import init_caches, prefill
    from repro.serving.scheduler import ContinuousBatchingEngine, Request

    cfg, dtype = get_config(MODEL), jnp.bfloat16
    n_req, n_slots, prompt_len, max_new = M_REQ, M_SLOTS, M_PROMPT, M_NEW
    # the KV ring advances once per decode call and once per prompt token,
    # so size it for every call of the run: it never wraps
    ring = n_req * (prompt_len + max_new)
    server = ModelServer(cfg, n_slots, max_len=ring, seed=seed, dtype=dtype)
    eng = ContinuousBatchingEngine(server.step_fn, server.prefill_request,
                                   n_slots)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(1, cfg.vocab, (n_req, prompt_len))
    reqs = [Request(rid=i, prompt=[int(t) for t in p],
                    max_new_tokens=max_new) for i, p in enumerate(prompts)]
    finite = []

    def sample(logits):
        finite.append(bool(np.isfinite(logits).all()))
        return logits.argmax(-1)

    eng.submit_batch(reqs)
    t0 = time.perf_counter()
    steps = 0
    while eng.stats.finished < n_req:
        if steps == 4 * n_req * max_new:
            raise RuntimeError(f"{eng.stats.finished}/{n_req} finished")
        eng.step(sample)
        steps += 1
    wall = time.perf_counter() - t0
    assert all(len(r.out_tokens) == max_new for r in reqs)
    assert finite and all(finite), "non-finite decode logits"

    caches = init_caches(cfg, n_req, prompt_len, dtype)
    want, _ = jax.jit(lambda p, b, c: prefill(p, cfg, b, c))(
        server.params, {"tokens": jnp.asarray(prompts, jnp.int32)}, caches)
    want = np.asarray(want, np.float32)
    got = np.stack([np.asarray(r._last_logits, np.float32) for r in reqs])
    assert got.shape == want.shape == (n_req, cfg.vocab)
    assert np.isfinite(got).all() and np.isfinite(want).all()
    rel = (np.linalg.norm(got - want, axis=1)
           / np.maximum(np.linalg.norm(want, axis=1), 1e-30))
    assert rel.max() <= MODEL_TOL, f"served vs prefill relative L2 {rel.max()}"
    return {
        "model": cfg.name,
        "params": int(sum(x.size for x in jax.tree.leaves(server.params))),
        "requests": n_req,
        "tokens": sum(len(r.out_tokens) for r in reqs),
        "engine_steps": steps,
        "serve_seconds": wall,
        "served_vs_prefill_rel_l2": float(rel.max()),
        "argmax_agree": int((got.argmax(1) == want.argmax(1)).sum()),
    }


# ---------------------------------------------------------------- main ----


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import setup_compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"compile cache: {setup_compile_cache()}")
    clock = CompileClock()
    phases = (
        ("kernels", lambda: kernels_phase(args.seed)),
        ("megastep", lambda: megastep_phase(args.seed)),
        ("model", lambda: model_phase(args.seed)),
    )
    for name, phase in phases:
        c0, n0, t0 = clock.seconds, clock.count, time.perf_counter()
        res = phase()
        res["phase_seconds"] = time.perf_counter() - t0
        res["compile_seconds"] = clock.seconds - c0
        res["compiles"] = clock.count - n0
        res["peak_bytes_in_use"] = (dev.memory_stats() or {}).get(
            "peak_bytes_in_use")
        print(f"{name}: {json.dumps(res)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
