"""Plain float32 reference of a decoder of full-attention and Mamba-2
blocks (the Granite 4.0-H family), for the tests: one whole sequence,
every product at ``HIGHEST`` precision, no cache, no chunking, no
batching, no kernels.  The Mamba-2 mixer runs its token-by-token
recurrence (`models.mamba2` scans in chunks).  It reads the program's
parameter tree (`transformer.init_params`) and nothing else of it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig

HI = jax.lax.Precision.HIGHEST


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE over a sequence x (L, heads, hd)."""
    L, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(L, dtype=np.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(cfg: ModelConfig, p, x):
    L, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("ld,dhk->lhk", x, p["wq"], precision=HI)
    k = jnp.einsum("ld,dhk->lhk", x, p["wk"], precision=HI)
    v = jnp.einsum("ld,dhk->lhk", x, p["wv"], precision=HI)
    if cfg.rope:
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * (cfg.attn_scale or hd**-0.5)
    s = jnp.where(np.tril(np.ones((L, L), bool)), s, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    return jnp.einsum("qhd,hdm->qm", ctx, p["wo"], precision=HI)


def _mamba2(cfg: ModelConfig, p, x):
    """The published recurrence, one token at a time, from a zero state."""
    H, P, N, di, K = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state,
                      cfg.mamba_inner, cfg.mamba_conv)
    L = x.shape[0]
    zxbcdt = jnp.matmul(x, p["in_proj"], precision=HI)
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * N], zxbcdt[:, 2 * di + 2 * N:]
    xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(sum(xp[i:i + L] * p["conv_w"][i] for i in range(K)) + p["conv_b"])
    xs, B, C = xbc[:, :di].reshape(L, H, P), xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = jnp.exp(dtt * A)[:, None, None] * h + (dtt[:, None] * xt)[..., None] * bt
        return h, jnp.einsum("hpn,n->hp", h, ct, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (xs, dt, B, C))
    y = (y + p["D"][:, None] * xs).reshape(L, di) * jax.nn.silu(z)
    return jnp.matmul(_rms(y, p["norm"], cfg.rms_eps), p["out_proj"], precision=HI)


def _mlp(p, x):
    g = jax.nn.silu(jnp.matmul(x, p["wg"], precision=HI))
    return jnp.matmul(g * jnp.matmul(x, p["wi"], precision=HI), p["wo"], precision=HI)


def _blocks(params, cfg: ModelConfig):
    """(kind, that layer's params) of every layer, in order."""
    out = [(b.kind, params["prefix"][i]) for i, b in enumerate(cfg.prefix)]
    for u in range(cfg.num_units):
        for j, b in enumerate(cfg.unit):
            out.append((b.kind, jax.tree.map(lambda a, u=u: a[u], params["units"][f"b{j}"])))
    out += [(b.kind, params["suffix"][i]) for i, b in enumerate(cfg.suffix)]
    return out


def forward_ref(params, cfg: ModelConfig, tokens):
    """Logits (L, vocab) of one sequence ``tokens`` (L,)."""
    if any((b.kind, b.window) not in (("attn", 0), ("mamba2", 0)) for b in cfg.blocks):
        raise ValueError("the reference holds full attention and Mamba-2 blocks only")
    if (cfg.mlp_kind != "swiglu" or cfg.norm_plus_one or cfg.sandwich_norms
            or cfg.embed_scale or cfg.qkv_bias):
        raise ValueError("the reference holds plain RMSNorm, a SwiGLU MLP and "
                         "attention without bias only")
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    r = cfg.residual_mult
    x = params["embed"][jnp.asarray(tokens)] * cfg.embed_mult
    for kind, p in _blocks(params, cfg):
        h = _rms(x, p["ln1"], cfg.rms_eps)
        x = x + r * (_attention(cfg, p["attn"], h) if kind == "attn" else _mamba2(cfg, p["mixer"], h))
        x = x + r * _mlp(p["mlp"], _rms(x, p["ln2"], cfg.rms_eps))
    h = _rms(x, params["final_ln"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    return jnp.matmul(h, head, precision=HI) / cfg.logits_div
