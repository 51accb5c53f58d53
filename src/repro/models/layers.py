"""Shared neural building blocks: RMSNorm, RoPE, GQA attention (full /
sliding-window, bias-optional), SwiGLU & classic MLPs.

Attention is implemented **blockwise** (online-softmax over KV blocks via
lax.scan) so activation memory is O(S·d) instead of O(S²) — this is both the
production path for 32k prefill and the pure-jnp oracle mirrored by
`kernels/flash_attention`.  A naive O(S²) reference lives in
`kernels/flash_attention/ref.py` for cross-checking.

Conventions: activations (B, S, D); params are plain dicts of jnp arrays;
compute dtype bf16 with fp32 softmax statistics; weights stored in the dtype
given at init (bf16 for large configs, fp32 for smoke tests).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..parallel.sharding import constrain

# ---------------------------------------------------------------- norms ----


def rms_norm(x, weight, eps: float = 1e-6, plus_one: bool = False):
    """RMSNorm; `plus_one` selects the Gemma convention ((1+w)·x̂)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    if plus_one:
        w = w + 1.0
    return (x32 * inv * w).astype(dtype)


def init_rms_norm(d, dtype, plus_one: bool = False):
    return jnp.zeros((d,), dtype) if plus_one else jnp.ones((d,), dtype)


# ----------------------------------------------------------------- rope ----


def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------ attention ----


def _online_softmax_block(carry, qk_scale, q, k, v, mask):
    """One KV-block step of the online-softmax recurrence.

    carry: (acc (B,H,Sq,hd) f32, m (B,H,Sq) f32, l (B,H,Sq) f32)
    q: (B,H,Sq,hd)  k,v: (B,H,Sk,hd)  mask: (B,1|H,Sq,Sk) bool (True=keep)
    """
    acc, m, l = carry
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * qk_scale
    s = jnp.where(mask, s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows (m_new == -inf)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return (acc_new, m_new, l_new)


def blockwise_attention(q, k, v, q_positions, kv_positions, *, window: int = 0,
                        kv_block: int = 1024, causal: bool = True, scale=None):
    """Flash-style attention with O(S) memory.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd); positions give the absolute
    index of each row (so decode passes Sq=1 with its position).
    GQA: H is grouped onto KV heads by repetition (H % KV == 0).
    window > 0 ⇒ sliding-window (key kept iff 0 ≤ qpos-kpos < window).
    ``scale`` multiplies QK^T (None: 1/sqrt(hd)).
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    assert H % KV == 0
    group = H // KV
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qh = jnp.transpose(q, (0, 2, 1, 3))  # (B,H,Sq,hd)
    kh = jnp.transpose(k, (0, 2, 1, 3))  # (B,KV,Sk,hd)
    vh = jnp.transpose(v, (0, 2, 1, 3))
    kh = jnp.repeat(kh, group, axis=1)  # (B,H,Sk,hd) — GQA repeat
    vh = jnp.repeat(vh, group, axis=1)
    # pin the repeated KV to the head sharding of q: without this the
    # partitioner resolves the q(heads-sharded) × k(kv-replicated) einsum by
    # replicating whichever side it fancies — at 32k context that is the
    # whole KV stream per chip.
    kh = constrain(kh, "batch", "heads", "seq")
    vh = constrain(vh, "batch", "heads", "seq")
    qh = constrain(qh, "batch", "heads", "seq")

    kv_block = min(kv_block, Sk)
    nblk = (Sk + kv_block - 1) // kv_block
    pad = nblk * kv_block - Sk
    if pad:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)), constant_values=-1)

    def mask_for(kp):
        dpos = q_positions[:, None, :, None] - kp[:, None, None, :]
        mask = kp[:, None, None, :] >= 0
        if causal:
            mask &= dpos >= 0
        if window > 0:
            mask &= dpos < window
        return mask

    acc0 = jnp.zeros((B, H, Sq, hd), jnp.float32)
    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)

    if nblk == 1:
        # single block — direct call, no one-trip loop
        acc, m, l = _online_softmax_block((acc0, m0, l0), scale, qh, kh, vh, mask_for(kv_positions))
    else:
        kh = kh.reshape(B, H, nblk, kv_block, hd).transpose(2, 0, 1, 3, 4)
        vh = vh.reshape(B, H, nblk, kv_block, hd).transpose(2, 0, 1, 3, 4)
        kpos = kv_positions.reshape(B, nblk, kv_block).transpose(1, 0, 2)  # (nblk,B,blk)

        # checkpoint the block body: without it the scan saves the per-block
        # probability matrices for backward — O(S²) memory, exactly what
        # flash attention exists to avoid. With it, backward recomputes each
        # block's s/p from the (already stored) k/v blocks.
        @jax.checkpoint
        def body(carry, blk):
            kb, vb, kp = blk
            return _online_softmax_block(carry, scale, qh, kb, vb, mask_for(kp)), None

        (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (kh, vh, kpos))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # (B,Sq,H,hd)


def single_query_attention(q, k, v, q_positions, kv_positions, *, window: int = 0,
                           scale=None, new=None, skip=None):
    """Decode-path attention (Sq == 1) against a (possibly sequence-sharded)
    KV cache, computed densely — no scan, no GQA head materialization.

    q: (B, 1, H, hd); k/v: (B, C, KV, hd); kv_positions: (B, C) with -1 for
    empty slots.  With the cache sequence dim sharded over the `model` axis
    (parallel/sharding.py "seq_kv" rule) the SPMD partitioner turns the
    softmax max/sum reductions and the PV contraction into exactly the
    flash-decode log-sum-exp merge: each shard attends to its sequence slice
    and partial results are combined with small all-reduces.  ``scale``
    multiplies QK^T (None: 1/sqrt(hd)).

    ``new`` (k, v), each (B, 1, KV, hd) in the cache's dtype: the query's
    own entry, attended beside the cache (kept where the query's position
    is ≥ 0), with cache slot ``skip`` (the one that entry is about to
    overwrite) left out — the same entries as after the write, with no
    new cache formed.
    """
    B, Sq, H, hd = q.shape
    _, C, KV, _ = k.shape
    assert Sq == 1 and H % KV == 0
    G = H // KV
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qg = q.reshape(B, KV, G, hd)  # (B,KV,G,hd) — GQA without repeating KV
    # flash-decode sharding contract: q is tiny — replicate it across the
    # model axis so the big KV keeps its *sequence* sharding; the partial
    # softmax stats and PV products then merge with small all-reduces (the
    # LSE merge).  Without the pin, GSPMD may instead reshard the cache to
    # match q's head sharding — replicating TBs of KV.
    qg = constrain(qg, "batch", None, None, None)
    # §Perf iteration 2 (KV streaming): keep K/V in their storage dtype and
    # accumulate in f32 via preferred_element_type — an explicit
    # .astype(f32) materializes a full-width copy of the WHOLE cache slice
    # (decode is memory-bound; this doubles its dominant traffic term).
    # Matches the Pallas decode kernel's numerics (bf16 operands, f32 acc).
    s = jnp.einsum("bkgd,bckd->bkgc", qg.astype(k.dtype), k,
                   preferred_element_type=jnp.float32) * scale
    s = constrain(s, "batch", None, None, "seq_kv")
    dpos = q_positions[:, None, None, :] - kv_positions[:, None, None, :]  # (B,1,1,C)
    mask = (kv_positions[:, None, None, :] >= 0) & (dpos >= 0)
    if window > 0:
        mask &= dpos < window
    if skip is not None:
        mask &= jnp.arange(C, dtype=jnp.int32) != skip
    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    if new is not None:
        kn, vn = new[0][:, 0], new[1][:, 0]  # (B, KV, hd)
        own = q_positions[:, :, None, None] >= 0  # (B, 1, 1, 1)
        sn = jnp.einsum("bkgd,bkd->bkg", qg.astype(kn.dtype), kn,
                        preferred_element_type=jnp.float32)[..., None] * scale
        m = jnp.maximum(m, sn)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    ctx = jnp.einsum("bkgc,bckd->bkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    if new is not None:
        pn = jnp.where(own, jnp.exp(sn - m), 0.0)
        l = l + pn
        ctx = ctx + pn.astype(vn.dtype).astype(jnp.float32) * vn[:, :, None, :]
    ctx = ctx / jnp.maximum(l, 1e-30)
    out = ctx.reshape(B, 1, H, hd).astype(q.dtype)
    # hand the o-projection a head-sharded ctx (reduce-scatter, not all-reduce)
    return constrain(out, "batch", "seq", "heads")


def chunk_attention(q, k, v, q_positions, ring_k, ring_v, ring_positions, *,
                    scale=None):
    """Prompt-chunk attention of one row: its T queries over its KV ring
    and the chunk's own keys, causal by position (a key is kept iff
    0 ≤ kpos ≤ qpos; position -1 marks an empty ring entry or padding).
    The ring row may hold only this sequence's earlier entries, so a chunk
    that starts the sequence (position 0) attends over its own keys alone
    and skips the ring.  Same numerics as `single_query_attention`: bf16
    operands in the cache's dtype, f32 scores, softmax and accumulation;
    ``scale`` multiplies QK^T (None: 1/sqrt(hd)).

    q: (1, T, H, hd); k/v: (1, T, KV, hd); q_positions (1, T);
    ring_k/ring_v: (1, C, KV, hd); ring_positions (1, C)."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qg = q.reshape(B, T, KV, G, hd).astype(ring_k.dtype)
    k, v = k.astype(ring_k.dtype), v.astype(ring_v.dtype)

    def attend(kk, vv, kpos):
        s = jnp.einsum("btkgd,bckd->bkgtc", qg, kk,
                       preferred_element_type=jnp.float32) * scale
        kp = kpos[:, None, None, None, :]
        mask = (kp >= 0) & (q_positions[:, None, None, :, None] >= kp)
        s = jnp.where(mask, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.where(mask, jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        ctx = jnp.einsum("bkgtc,bckd->bkgtd", p.astype(vv.dtype), vv,
                         preferred_element_type=jnp.float32)
        ctx = ctx / jnp.maximum(l, 1e-30)
        return ctx.transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd).astype(q.dtype)

    return jax.lax.cond(
        q_positions[0, 0] == 0,
        lambda: attend(k, v, q_positions),
        lambda: attend(jnp.concatenate([ring_k, k], axis=1),
                       jnp.concatenate([ring_v, v], axis=1),
                       jnp.concatenate([ring_positions, q_positions], axis=1)))


# -------------------------------------------------------- attention block ---


def init_attention(key, cfg_layer, d_model, dtype):
    """cfg_layer: dict with n_heads, n_kv_heads, head_dim, qkv_bias."""
    H, KV, hd = cfg_layer["n_heads"], cfg_layer["n_kv_heads"], cfg_layer["head_dim"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = d_model**-0.5
    p = {
        "wq": (jax.random.normal(k1, (d_model, H, hd)) * std).astype(dtype),
        "wk": (jax.random.normal(k2, (d_model, KV, hd)) * std).astype(dtype),
        "wv": (jax.random.normal(k3, (d_model, KV, hd)) * std).astype(dtype),
        "wo": (jax.random.normal(k4, (H, hd, d_model)) * (H * hd) ** -0.5).astype(dtype),
    }
    if cfg_layer.get("qkv_bias", False):
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((KV, hd), dtype)
        p["bv"] = jnp.zeros((KV, hd), dtype)
    return p


def qkv_project(p, x, positions, rope_theta=10000.0, rope: bool = True):
    """x (B,S,D) → q (B,S,H,hd), k/v (B,S,KV,hd), RoPE applied (unless
    ``rope`` is False: attention without a position embedding)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    return q, k, v


def attn_output(p, ctx):
    """ctx (B,S,H,hd) → (B,S,D) via the o-projection."""
    return jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])


# ------------------------------------------------------------------ mlps ----


def init_swiglu(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": (jax.random.normal(k1, (d_model, d_ff)) * d_model**-0.5).astype(dtype),
        "wg": (jax.random.normal(k2, (d_model, d_ff)) * d_model**-0.5).astype(dtype),
        "wo": (jax.random.normal(k3, (d_ff, d_model)) * d_ff**-0.5).astype(dtype),
    }


def swiglu_forward(p, x):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["wg"])) * jnp.einsum("bsd,df->bsf", x, p["wi"])
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


def init_gelu_mlp(key, d_model, d_ff, dtype):
    k1, k2 = jax.random.split(key, 2)
    return {
        "wi": (jax.random.normal(k1, (d_model, d_ff)) * d_model**-0.5).astype(dtype),
        "wo": (jax.random.normal(k2, (d_ff, d_model)) * d_ff**-0.5).astype(dtype),
    }


def gelu_mlp_forward(p, x):
    return jnp.einsum("bsf,fd->bsd", jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["wi"])), p["wo"])


def geglu_forward(p, x):
    """GeGLU (Griffin/Gemma MLP): gelu-gated — same params as swiglu."""
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["wg"])) * jnp.einsum("bsd,df->bsf", x, p["wi"])
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


MLP_INIT = {"swiglu": init_swiglu, "geglu": init_swiglu, "gelu": init_gelu_mlp}
MLP_FWD = {"swiglu": swiglu_forward, "geglu": geglu_forward, "gelu": gelu_mlp_forward}


# --------------------------------------------------------------- helpers ----


def init_embedding(key, vocab, d_model, dtype):
    return (jax.random.normal(key, (vocab, d_model)) * d_model**-0.5).astype(dtype)


partial  # re-export guard (silence linters for unused import style)
