"""Mamba-2 mixer (SSD, arXiv:2405.21060) with n_groups 1, as Granite 4.0-H
publishes it (HF ``GraniteMoeHybridMambaLayer``):

    in_proj: x → [z (di) | xBC (di + 2N) | dt (H)]
    xBC = SiLU(causal depthwise conv1d(xBC) + conv bias); xBC → x, B, C
    dt = softplus(dt + dt_bias),  A = −exp(A_log)          (per head)
    h_t = exp(dt_t·A)·h_{t−1} + dt_t·x_t B_tᵀ     (per head: P × N)
    y_t = h_t C_t + D ⊙ x_t
    out = out_proj(RMSNorm(y ⊙ SiLU(z)) · norm)   (over all di channels)

A sequence (modes ``train``, ``prefill``, ``chunk``) is scanned in the
chunked SSD form, chunks of ``cfg.mamba_chunk`` tokens; one decode token
takes the one-step recurrence.  The state is float32: the SSM state
(B, H, P, N) and the conv's last K−1 inputs (B, K−1, di + 2N).

A position of −1 (padding at the end of a chunk, or a row that takes no
part in a decode call) leaves the state as it was: its dt is zero, so its
step decays by exp(0) = 1 and adds nothing, and the conv tail keeps the
last K−1 valid inputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .cache import make_mamba2_state

HI = jax.lax.Precision.HIGHEST


def init_mamba2(key, d_model, cfg, dtype):
    """Mamba-2's usual init: A = −[1, 16), dt in [0.001, 0.1) through
    dt_bias, D = 1."""
    H, di, cd, K = cfg.mamba_heads, cfg.mamba_inner, cfg.mamba_conv_dim, cfg.mamba_conv
    ks = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(ks[3], (H,), minval=jnp.log(1e-3), maxval=jnp.log(0.1)))
    return {
        "in_proj": (jax.random.normal(ks[0], (d_model, di + cd + H)) * d_model**-0.5).astype(dtype),
        "conv_w": (jax.random.normal(ks[1], (K, cd)) * K**-0.5).astype(dtype),
        "conv_b": jnp.zeros((cd,), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),  # softplus⁻¹
        "A_log": jnp.log(jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)).astype(dtype),
        "D": jnp.ones((H,), dtype),
        "norm": jnp.ones((di,), dtype),
        "out_proj": (jax.random.normal(ks[4], (di, d_model)) * di**-0.5).astype(dtype),
    }


def _segsum(a):
    """a (..., T) → (..., T, T): out[i, j] = a[j+1] + … + a[i] for i ≥ j,
    −inf above the diagonal (a masked cumsum: no differences of large
    partial sums)."""
    T = a.shape[-1]
    x = jnp.where(jnp.tri(T, k=-1, dtype=bool), a[..., :, None], 0.0)
    x = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tri(T, dtype=bool), x, -jnp.inf)


def ssd_scan(x, dt, A, B, C, h0, chunk: int):
    """The SSD recurrence over a sequence, in chunks (Mamba-2's "minimal
    discrete" form): exact attention-like products inside each chunk, the
    state carried between chunks.  float32 throughout.

    x (b, S, H, P), dt (b, S, H) (already softplus'ed; 0 at padding),
    A (H,) negative, B and C (b, S, N), h0 (b, H, P, N).
    Returns y (b, S, H, P) without the D skip, and the final state."""
    b, S, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    pad = -S % Q
    if pad:  # dt 0 and zero inputs: the padded steps change nothing
        z = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))  # noqa: E731
        x, dt, B, C = z(x), z(dt), z(B), z(C)
    c = x.shape[1] // Q
    X = (x * dt[..., None]).reshape(b, c, Q, H, P)
    Ad = (dt * A).reshape(b, c, Q, H).transpose(0, 3, 1, 2)  # (b,H,c,Q)
    Bc, Cc = B.reshape(b, c, Q, N), C.reshape(b, c, Q, N)
    Acs = jnp.cumsum(Ad, axis=-1)
    # within each chunk
    L = jnp.exp(_segsum(Ad))  # (b,H,c,Q,Q)
    CB = jnp.einsum("bcln,bcsn->bcls", Cc, Bc, precision=HI)
    y = jnp.einsum("bcls,bhcls,bcshp->bclhp", CB, L, X, precision=HI)
    # each chunk's own contribution to the state at its end
    decay = jnp.exp(Acs[..., -1:] - Acs)  # (b,H,c,Q)
    st = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay, X, precision=HI)
    # the state entering each chunk, and the final one
    st = jnp.concatenate([h0[:, None].astype(jnp.float32), st], axis=1)
    dchunk = jnp.exp(_segsum(jnp.pad(Acs[..., -1], ((0, 0), (0, 0), (1, 0)))))
    st = jnp.einsum("bhzc,bchpn->bzhpn", dchunk, st, precision=HI)
    entering, final = st[:, :-1], st[:, -1]
    y = y + jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, entering, jnp.exp(Acs),
                       precision=HI)
    return y.reshape(b, c * Q, H, P)[:, :S], final


def ssd_step(x, dt, A, B, C, h):
    """One token: x (b, H, P), dt (b, H), B and C (b, N), h (b, H, P, N)."""
    h = h * jnp.exp(dt * A)[..., None, None] + (dt[..., None] * x)[..., None] * B[:, None, None, :]
    return jnp.einsum("bhpn,bn->bhp", h, C, precision=HI), h


def _conv(xbc, w, bias, tail, n_valid):
    """Causal depthwise conv with bias and SiLU, in float32.  ``tail``
    holds the K−1 inputs before this call; the new tail is the K−1 inputs
    before each row's first invalid position (``n_valid`` (b,))."""
    K = w.shape[0]
    S = xbc.shape[1]
    xp = jnp.concatenate([tail, xbc.astype(jnp.float32)], axis=1)
    w = w.astype(jnp.float32)
    out = sum(xp[:, i:i + S] * w[i] for i in range(K)) + bias.astype(jnp.float32)
    new_tail = jax.vmap(lambda r, n: jax.lax.dynamic_slice_in_dim(r, n, K - 1, 0))(xp, n_valid)
    return jax.nn.silu(out), new_tail


def mamba2_forward(p, cfg, x, positions, state, decode: bool):
    """x (b, S, d) normed input, positions (b, S) (−1: leave the state as
    it is), ``state`` as `cache.make_mamba2_state` (None: zeros).  Returns
    (mixer output (b, S, d) in x's dtype, new state)."""
    b, S, _ = x.shape
    H, P, N, di = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state, cfg.mamba_inner
    if state is None:
        state = make_mamba2_state(b, H, P, N, cfg.mamba_conv_dim, cfg.mamba_conv)
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * N], axis=-1)
    valid = positions >= 0
    xbc, tail = _conv(xbc, p["conv_w"], p["conv_b"], state["conv"],
                      jnp.sum(valid, axis=1, dtype=jnp.int32))
    xs, Bm, Cm = jnp.split(xbc, [di, di + N], axis=-1)
    xs = xs.reshape(b, S, H, P)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    dt = jnp.where(valid[..., None], dt, 0.0)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    if decode:
        y, h = ssd_step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], state["ssm"])
        y = y[:, None]
    else:
        y, h = ssd_scan(xs, dt, A, Bm, Cm, state["ssm"], cfg.mamba_chunk)
    y = y + p["D"].astype(jnp.float32)[:, None] * xs
    y = y.reshape(b, S, di) * jax.nn.silu(z.astype(jnp.float32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_eps)
    y = (y * p["norm"].astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"]), {"ssm": h, "conv": tail}
