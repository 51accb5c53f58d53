"""Plain reference of the served model, and its int8 control.

A Qwen2-style decoder written from the published description (arXiv:
2407.10671; RMSNorm, rotate-half RoPE, grouped-query attention with q/k/v
bias, SwiGLU), in float32 with every matmul at ``HIGHEST`` precision,
over whole sequences with a causal mask: no cache, no batching, no
kernels.  It imports nothing of the program and makes its weights again
from the seed (`model.layer_leaf`), one layer at a time, so it fits
beside nothing else once the program's state is freed.

`served_gaps` teacher-forces each sampled prompt with the tokens the
program served, and reads at every served position the gap by which the
served token's logit lies below the reference's best.  With
``control=True`` it also runs the control: the same forward with every
linear layer (q, k, v, o, gate, up, down and the LM head) computed in
int8 — weights per output channel and activations per row, symmetric —
and reads the gap of the token that the control puts first.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .model import ModelSpec, layer_leaf, leaves, seed_key

HI = jax.lax.Precision.HIGHEST


def bucket(n: int) -> int:
    """Padded sequence length: a power of two, at least 256."""
    p = 256
    while p < n:
        p *= 2
    return p


def _mm(a, w, quant: bool):
    """``a`` (..., k) @ ``w`` (k, n) in f32, or in emulated int8."""
    if not quant:
        return jnp.matmul(a, w, precision=HI)
    sa = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 127.0
    sa = jnp.where(sa > 0, sa, 1.0)
    sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    sw = jnp.where(sw > 0, sw, 1.0)
    aq = jnp.clip(jnp.round(a / sa), -127, 127)
    wq = jnp.clip(jnp.round(w / sw), -127, 127)
    return jnp.matmul(aq, wq, precision=HI) * sa * sw


def _rms(x, w, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _rope(x, theta):
    L, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(spec: ModelSpec, quant: bool, x, W):
    """One decoder layer over a whole sequence ``x`` (L, d)."""
    L, d = x.shape
    H, KV, hd = spec.heads, spec.kv_heads, spec.head_dim
    h = _rms(x, W["ln1"], spec.rms_eps)
    q = _mm(h, W["wq"].reshape(d, H * hd), quant).reshape(L, H, hd) + W["bq"]
    k = _mm(h, W["wk"].reshape(d, KV * hd), quant).reshape(L, KV, hd) + W["bk"]
    v = _mm(h, W["wv"].reshape(d, KV * hd), quant).reshape(L, KV, hd) + W["bv"]
    q, k = _rope(q, spec.rope_theta), _rope(k, spec.rope_theta)
    g = H // KV
    qg = q.reshape(L, KV, g, hd)
    s = jnp.einsum("qkgd,ckd->kgqc", qg, k, precision=HI) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("kgqc,ckd->qkgd", p, v, precision=HI).reshape(L, H * hd)
    x = x + _mm(ctx, W["wo"].reshape(H * hd, d), quant)
    h = _rms(x, W["ln2"], spec.rms_eps)
    ff = jax.nn.silu(_mm(h, W["wg"], quant)) * _mm(h, W["wi"], quant)
    return x + _mm(ff, W["wo2"], quant)


def _final(spec: ModelSpec, x_ref, x_ctl, norm, head, pos, toks):
    """Gaps at the served positions ``pos`` (n,) of the tokens ``toks``."""
    def logits(x, quant):
        h = _rms(x[pos], norm, spec.rms_eps)
        return _mm(h, head, quant)

    ref = logits(x_ref, False)
    best = jnp.max(ref, -1)
    served_gap = best - jnp.take_along_axis(ref, toks[:, None], -1)[:, 0]
    if x_ctl is None:
        return served_gap, None
    ctl_top = jnp.argmax(logits(x_ctl, True), -1)
    ctl_gap = best - jnp.take_along_axis(ref, ctl_top[:, None], -1)[:, 0]
    return served_gap, ctl_gap


_LAYER_NAMES = {  # reference name → the leaf path it is drawn under
    "ln1": "units/b0/ln1", "ln2": "units/b0/ln2",
    "wq": "units/b0/attn/wq", "wk": "units/b0/attn/wk",
    "wv": "units/b0/attn/wv", "wo": "units/b0/attn/wo",
    "bq": "units/b0/attn/bq", "bk": "units/b0/attn/bk",
    "bv": "units/b0/attn/bv", "wi": "units/b0/mlp/wi",
    "wg": "units/b0/mlp/wg", "wo2": "units/b0/mlp/wo",
}


def served_gaps(spec: ModelSpec, seed: int, samples, control: bool = False):
    """``samples``: list of (prompt tokens, served tokens).  Returns the
    per-token gaps of the served tokens, concatenated over samples, and
    those of the control's tokens (None without ``control``)."""
    key = seed_key(seed)
    specs = leaves(spec)
    bf16 = jnp.bfloat16

    @jax.jit
    def gen_layer(key, layer):
        return {n: layer_leaf(key, p, specs[p], layer, bf16).astype(jnp.float32)
                for n, p in _LAYER_NAMES.items()}

    gen = jax.jit(lambda key, path: layer_leaf(
        key, path, specs[path], 0, bf16).astype(jnp.float32),
        static_argnums=1)
    layer_ref = jax.jit(partial(_layer, spec, False))
    layer_ctl = jax.jit(partial(_layer, spec, True))
    final = jax.jit(partial(_final, spec))

    embed = gen(key, "embed")
    xs, xc, meta = [], [], []
    for prompt, served in samples:
        seq = list(prompt) + list(served)
        P = bucket(len(seq))
        ids = jnp.asarray(seq + [0] * (P - len(seq)), jnp.int32)
        xs.append(embed[ids])
        xc.append(embed[ids] if control else None)
        # served token i is predicted at position len(prompt) - 1 + i;
        # padded to P so that one program serves every sample of a bucket
        pos = np.zeros(P, np.int32)
        toks = np.zeros(P, np.int32)
        pos[:len(served)] = np.arange(len(prompt) - 1,
                                      len(prompt) - 1 + len(served))
        toks[:len(served)] = served
        meta.append((jnp.asarray(pos), jnp.asarray(toks), len(served)))
    head = embed.T if spec.tied else gen(key, "head")
    del embed
    for layer in range(spec.layers):
        W = gen_layer(key, jnp.int32(layer))
        xs = [layer_ref(x, W) for x in xs]
        if control:
            xc = [layer_ctl(x, W) for x in xc]
    norm = gen(key, "final_ln")
    served, ctl = [], []
    for x, c, (pos, toks, n) in zip(xs, xc, meta):
        g, cg = final(x, c, norm, head, pos, toks)
        served.append(np.asarray(g)[:n])
        if control:
            ctl.append(np.asarray(cg)[:n])
    return (np.concatenate(served),
            np.concatenate(ctl) if control else None)
