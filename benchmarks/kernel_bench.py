"""Kernel micro-benchmarks.

Off the TPU the Pallas kernels execute in interpret mode
(`kernels.ops.use_interpret`), so wall-clock is meaningless; what is
reported instead:
  * correctness deltas vs the jnp oracles (allclose margins);
  * the *analytic* VMEM working set per BlockSpec configuration vs the
    16 MiB/core budget (the quantity that determines real TPU viability);
  * arithmetic intensity per kernel config (drives the §Roofline discussion).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.admission.functional_qos import QoSState, make_qos, qos_take
from repro.core.functional import live_fifo_rank, live_fifo_rank_pairwise
from repro.kernels.decode_attention import decode_attention
from repro.kernels.ops import use_interpret
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.qos_admission import qos_round_fused
from repro.kernels.ref import (
    decode_attention_ref,
    mha_ref,
    qos_round_ref,
    sema_batch_ref,
)
from repro.kernels.sema_batch import sema_batch

VMEM_BUDGET = 16 * 2**20


def qos_vmem(block_n, s_pad, u_pad, table):
    """Fused qos_round working set: row blocks, tenant state, crossings,
    the (Sp, T) permutation-poke compare, and the two tri matmuls."""
    rows = 3 * block_n * 4 + 2 * block_n * 4            # in + out row blocks
    tenant = (2 + 4 + 4 + 1) * s_pad * 4 + 6 * s_pad * 4  # state + scratch
    seq = 2 * table * 4
    crossings = s_pad * u_pad * 4 * 2                   # cross + key
    poke = s_pad * table * 4
    tri = block_n * block_n * 4 + s_pad * s_pad * 4
    return rows + tenant + seq + crossings + poke + tri


def _flops(fn, *args):
    return (jax.jit(fn).lower(*args).compile().cost_analysis()
            or {}).get("flops", 0.0)


def flash_vmem(block_q, block_k, hd, G):
    """fp32 scratch + bf16 tiles + double buffering of k/v blocks."""
    scratch = (G * block_q * hd + 2 * G * block_q) * 4
    tiles = (G * block_q * hd + 2 * 2 * block_k * hd) * 2  # q + 2×(k,v) dbuf
    probs = G * block_q * block_k * 4
    return scratch + tiles + probs


def run(metrics: dict | None = None) -> str:
    lines = ["== Pallas kernels (interpret-mode validation + VMEM budgets) =="]
    key = jax.random.PRNGKey(0)
    interp = use_interpret()

    # flash attention configs: (name, S, H, KV, hd, bq, bk)
    for name, S, H, KV, hd, bq, bk in [
        ("qwen2-72b prefill tile", 512, 8, 1, 128, 256, 512),
        ("gemma3 local-window    ", 512, 4, 1, 256, 256, 256),
        ("musicgen               ", 512, 4, 4, 64, 512, 512),
    ]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (1, S, H, hd), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, S, KV, hd), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, S, KV, hd), jnp.bfloat16)
        out = flash_attention_fwd(q, k, v, block_q=bq, block_k=bk, interpret=interp)
        ref = mha_ref(q, k, v)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
        G = H // KV
        vm = flash_vmem(bq, bk, hd, G)
        flops = 4 * S * S * hd  # per (b,kv-head)
        bytes_hbm = (S * G * hd + 2 * S * hd) * 2
        lines.append(
            f"flash {name} bq={bq} bk={bk}: err={err:.1e} "
            f"VMEM={vm / 2**20:.1f}MiB ({'OK' if vm < VMEM_BUDGET else 'OVER'}) "
            f"AI={flops / bytes_hbm:.0f} flop/B")
        if metrics is not None:
            metrics.setdefault("oracle_err", {})[f"flash/{name.strip()}"] = err

    # decode attention
    for name, C, H, KV, hd, bk in [
        ("72b decode shard  ", 2048, 64, 8, 128, 512),
        ("long-context shard", 2048, 4, 1, 256, 512),
    ]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (2, H, hd), jnp.bfloat16)
        k = jax.random.normal(ks[1], (2, C, KV, hd), jnp.bfloat16)
        v = jax.random.normal(ks[2], (2, C, KV, hd), jnp.bfloat16)
        pos = jnp.broadcast_to(jnp.arange(C)[None], (2, C)).astype(jnp.int32)
        qp = jnp.full((2,), C, jnp.int32)
        out = decode_attention(q, k, v, pos, qp, block_k=bk, interpret=interp)
        ref = decode_attention_ref(q, k, v, pos, qp)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
        G = H // KV
        vm = (G * hd * 4 + 2 * G * 4 + 2 * 2 * bk * hd * 2 + G * bk * 4)
        ai = (4 * C * hd * G) / (2 * C * hd * 2)  # ≈ 2·G flop/B — memory-bound
        lines.append(
            f"decode {name} bk={bk}: err={err:.1e} VMEM={vm / 2**20:.2f}MiB "
            f"AI={ai:.1f} flop/B (memory-bound by design)")
        if metrics is not None:
            metrics.setdefault("oracle_err", {})[f"decode/{name.strip()}"] = err

    # paged prefill (chunked-prefill kernel: in-pass pool writeback)
    from repro.kernels.paged_prefill import paged_prefill
    from repro.kernels.ref import paged_prefill_ref

    S, CT, H, KV, hd = 4, 32, 4, 2, 64
    NB, BS, MB = 64, 16, 8
    ks = jax.random.split(key, 5)
    qp_ = jax.random.normal(ks[0], (S, CT, H, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (S, CT, KV, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (S, CT, KV, hd), jnp.float32)
    kpool = jax.random.normal(ks[3], (NB, BS, KV, hd), jnp.float32)
    vpool = jax.random.normal(ks[4], (NB, BS, KV, hd), jnp.float32)
    tbl = jnp.arange(S * MB, dtype=jnp.int32).reshape(S, MB) % NB
    offs = jnp.asarray([0, 24, 7, 40], jnp.int32)
    lens = jnp.asarray([32, 32, 17, 0], jnp.int32)
    out_k, kp2, vp2 = paged_prefill(qp_, kc, vc, kpool, vpool, tbl, offs,
                                    lens, interpret=interp)
    out_r, kpr, vpr = paged_prefill_ref(qp_, kc, vc, kpool, vpool, tbl,
                                        offs, lens)
    pf_exact = bool(np.array_equal(np.asarray(out_k), np.asarray(out_r))
                    and np.array_equal(np.asarray(kp2), np.asarray(kpr))
                    and np.array_equal(np.asarray(vp2), np.asarray(vpr)))
    G = H // KV
    # q/chunk tiles + 2×(k,v) block dbuf + merge one-hot + scores + scratch
    vm_pf = (G * CT * hd * 4 + 2 * CT * hd * 4 + 2 * 2 * BS * hd * 4
             + BS * CT * 4 + G * CT * BS * 4
             + (G * CT * hd + 2 * G * CT) * 4)
    lines.append(
        f"paged_prefill S={S} CT={CT} BS={BS} (ragged offs, idle slot, "
        f"GQA {G}): bit-exact={pf_exact} incl. in-pass pool writeback; "
        f"VMEM={vm_pf / 2**20:.2f}MiB")
    if metrics is not None:
        metrics.setdefault("oracle_err", {})["paged_prefill/bitexact"] = \
            0.0 if pf_exact else 1.0

    # sema_batch
    req = jax.random.bernoulli(key, 0.6, (2048,))
    out = sema_batch(jnp.uint32(0), jnp.uint32(64), jnp.zeros((1024,), jnp.uint32),
                     req, jnp.uint32(128), jnp.uint32(7), block_n=512, interpret=interp)
    ref = sema_batch_ref(jnp.uint32(0), jnp.uint32(64), jnp.zeros((1024,), jnp.uint32),
                         req, jnp.uint32(128), jnp.uint32(7))
    exact = bool(np.array_equal(np.asarray(out[4]), np.asarray(ref["admitted"])))
    lines.append(f"sema_batch 2048 reqs × 1024 buckets: exact={exact} "
                 f"(tri-matmul rank + permutation one-hot poke)")
    if metrics is not None:
        metrics["sema_batch_exact"] = exact

    # fused QoS admission round: kernel vs functional oracle (bit-exact)
    S, N, TBL, MU, BN = 8, 512, 512, 32, 128
    rng = np.random.default_rng(0)
    qs = make_qos(np.linspace(1, 4, S).astype(np.float32), table_size=TBL)
    ids = jnp.asarray(rng.integers(0, S, N), jnp.int32)
    qs, tk, _, _ = qos_take(qs, ids, jnp.ones(N, bool))
    alive = jnp.asarray(rng.random(N) > 0.15)
    dls = jnp.asarray(np.where(rng.random(N) > 0.4,
                               rng.uniform(0, 2, N), np.inf), jnp.float32)
    ref = qos_round_ref(qs, ids, tk, alive, dls, 1.0, 24, MU)
    ks, ka, ke, kl = qos_round_fused(qs, ids, tk, alive, dls, 1.0, 24,
                                     max_units=MU, block_n=BN, interpret=interp)
    qexact = (
        np.array_equal(np.asarray(ka), np.asarray(ref["admitted"]))
        and np.array_equal(np.asarray(ke), np.asarray(ref["expired"]))
        and int(kl) == int(ref["leftover"])
        and all(np.array_equal(np.asarray(getattr(ks, f)),
                               np.asarray(getattr(ref["state"], f)))
                for f in QoSState._fields))
    s_pad, u_pad = 128, 128
    vm = qos_vmem(BN, s_pad, u_pad, TBL)
    lines.append(
        f"qos_round {N} rows × {S} tenants × {TBL} buckets: exact={qexact} "
        f"VMEM={vm / 2**20:.2f}MiB ({'OK' if vm < VMEM_BUDGET else 'OVER'}) "
        f"(2-phase grid: depth sweep → bit-descend stride alloc + "
        f"permutation poke → tri-rank admit)")
    if metrics is not None:
        metrics["qos_round_exact"] = qexact

    # reference-path asymptotics: blocked-prefix live rank vs the retained
    # O(N²) pairwise path, measured XLA flops at N=4k (the acceptance gate:
    # the new path must beat the old asymptotically, not just on wall time)
    N4, S4 = 4096, 8
    ids4 = jnp.asarray(rng.integers(0, S4, N4), jnp.int32)
    tk4 = jnp.arange(N4, dtype=jnp.uint32)
    al4 = jnp.asarray(rng.random(N4) > 0.2)
    fl_new = _flops(lambda i, t, a: live_fifo_rank(i, t, a, S4), ids4, tk4, al4)
    fl_old = _flops(live_fifo_rank_pairwise, ids4, tk4, al4)
    if fl_old > 0:  # some backends report no cost analysis — skip, don't fail
        assert fl_new < fl_old / 10, (
            f"blocked-prefix rank not asymptotically better: {fl_new:.3g} vs "
            f"pairwise {fl_old:.3g} flops at N={N4}")
    lines.append(
        f"live_fifo_rank N={N4} S={S4}: blocked-prefix {fl_new:.3g} flops "
        f"vs pairwise {fl_old:.3g} ({fl_old / max(fl_new, 1):.0f}× fewer; "
        f"O(N·S/block) vs O(N²))")
    if metrics is not None:
        metrics["qos_rank_flops"] = {
            "n": N4, "s": S4, "blocked": fl_new, "pairwise": fl_old,
            "ratio": fl_old / max(fl_new, 1.0)}

    # per-kernel cost-analysis profile: XLA's own flops / bytes-accessed
    # view of each serving kernel's compiled module (interpret mode lowers
    # to plain HLO, so the numbers are the reference-path cost — the
    # groundwork for the ROADMAP item-2 TPU roofline validation).  Some
    # backends report no cost model: rows degrade to zeros, never fail.
    from repro.kernels.paged_decode import paged_decode

    def _profile(name, fn, *args):
        try:
            ca = jax.jit(fn).lower(*args).compile().cost_analysis() or {}
        except Exception as e:  # pragma: no cover - backend-specific
            lines.append(f"profile {name}: cost analysis unavailable ({e})")
            ca = {}
        flops = float(ca.get("flops", 0.0))
        byt = float(ca.get("bytes accessed", 0.0))
        ai = flops / byt if byt else float("nan")
        lines.append(f"profile {name}: {flops:.3g} flops, {byt:.3g} B "
                     f"accessed, AI={ai:.2f} flop/B")
        if metrics is not None:
            metrics.setdefault("kernel_profile", {})[name] = {
                "flops": flops, "bytes": byt}

    _profile("qos_round_fused",
             lambda st, i, t, a, d: qos_round_fused(
                 st, i, t, a, d, 1.0, 24, max_units=MU, block_n=BN,
                 interpret=interp),
             qs, ids, tk, alive, dls)
    pd_q = jax.random.normal(key, (tbl.shape[0], H, hd), jnp.float32)
    pd_lens = jnp.asarray([32, 17, 9, 0], jnp.int32)
    _profile("paged_decode",
             lambda q_, kp_, vp_, t_, l_: paged_decode(
                 q_, kp_, vp_, t_, l_, interpret=interp),
             pd_q, kpool, vpool, tbl, pd_lens)
    _profile("paged_prefill",
             lambda *a: paged_prefill(*a, interpret=interp),
             qp_, kc, vc, kpool, vpool, tbl, offs, lens)
    return "\n".join(lines)


if __name__ == "__main__":
    print(run())
