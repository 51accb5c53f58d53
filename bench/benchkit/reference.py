"""The pieces of a plain reference that belong to no architecture.

A family's `served_gaps` (`bench/families/<architecture>.py`) is its
model written from the published description, in float32 with every
matmul at ``HIGHEST`` precision, over whole sequences with a causal mask:
no cache, no batching, no kernels.  It imports nothing of the program and
makes its weights again from the seed (`model.layer_leaf`), one layer at
a time, so it fits beside nothing else once the program's state is freed.
It teacher-forces each sampled prompt with the tokens the program served,
and reads at every served position the gap by which the served token's
logit lies below the reference's best.  Its control computes every linear
layer in int8 — weights per output channel and activations per row,
symmetric (`mm` with ``quant``) — and reads the gap of the token that the
control puts first (`final`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def bucket(n: int) -> int:
    """Padded sequence length: a power of two, at least 256."""
    p = 256
    while p < n:
        p *= 2
    return p


def pad_sample(prompt, served):
    """One sample as the program of its bucket takes it: the sequence's ids
    padded to `bucket`; the positions at which each served token is
    predicted (served token i at ``len(prompt) - 1 + i``) and the tokens,
    padded alike, so that one program serves every sample of a bucket;
    and how many tokens were served."""
    seq = list(prompt) + list(served)
    P = bucket(len(seq))
    ids = jnp.asarray(seq + [0] * (P - len(seq)), jnp.int32)
    pos = np.zeros(P, np.int32)
    toks = np.zeros(P, np.int32)
    pos[:len(served)] = np.arange(len(prompt) - 1,
                                  len(prompt) - 1 + len(served))
    toks[:len(served)] = served
    return ids, jnp.asarray(pos), jnp.asarray(toks), len(served)


def mm(a, w, quant: bool):
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


def rms(x, w, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def rope(x, theta):
    """Rotate-half RoPE over a whole sequence ``x`` (L, heads, hd)."""
    L, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def final(eps, x_ref, x_ctl, norm, head, pos, toks):
    """Gaps at the served positions ``pos`` (n,) of the tokens ``toks``,
    after an RMSNorm of epsilon ``eps`` with scale ``norm`` and the LM
    head ``head`` (d, vocab); the control's (``x_ctl`` not None) are the
    gaps of its own first tokens."""
    def logits(x, quant):
        h = rms(x[pos], norm, eps)
        return mm(h, head, quant)

    ref = logits(x_ref, False)
    best = jnp.max(ref, -1)
    served_gap = best - jnp.take_along_axis(ref, toks[:, None], -1)[:, 0]
    if x_ctl is None:
        return served_gap, None
    ctl_top = jnp.argmax(logits(x_ctl, True), -1)
    ctl_gap = best - jnp.take_along_axis(ref, ctl_top[:, None], -1)[:, 0]
    return served_gap, ctl_gap
