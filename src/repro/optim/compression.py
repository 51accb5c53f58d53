"""Int8 error-feedback gradient compression for the scarce inter-pod links.

The pod axis crosses data-center interconnect (~9 GB/s/chip assumed) while
intra-pod ICI runs ~50 GB/s/link, so the multi-pod gradient reduction is the
dominant collective.  We therefore reduce gradients hierarchically:

    g_local   = reduce(g, axis="data")           # fast ICI, full precision
    absmax    = pmax(blockmax(g_local + e), "pod")   # tiny fp32 collective
    q         = round((g_local + e)/scale)       # int8-range values
    q_sum     = psum(q as int16, "pod")          # 2-byte wire (4-byte fp32 → 2x;
                                                 # real HW reduces the int8
                                                 # payload → 4x)
    g_global  = q_sum * scale / n_pods
    e'        = (g_local + e) - q*scale          # error feedback (stays local)

The *shared* (pmax'ed) scale makes the integer psum exact: Σ qᵢ·s == (Σ qᵢ)·s.
Error feedback makes quantization unbiased over time — the residual `e` lives
in the optimizer state and is re-injected next step, so compression noise
does not accumulate into the trajectory (standard EF-SGD result; validated in
tests/test_compression.py).

Used when the mesh has a "pod" axis and the run config enables
`compress_pod_grads`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 256  # per-block scale granularity (flattened)


def _blocked(x):
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % BLOCK
    return jnp.pad(flat, (0, pad)).reshape(-1, BLOCK), flat.size


def compress_psum(g, residual, axis_name: str, n_shards: int):
    """Error-feedback int8-range psum over `axis_name` for one leaf.

    Returns (g_mean fp32 (mean over shards), new_residual fp32).
    """
    x = g.astype(jnp.float32) + residual
    blocks, n = _blocked(x)
    absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    absmax = jax.lax.pmax(absmax, axis_name)  # shared scale → exact int sum
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127)
    local_dq = (q * scale).reshape(-1)[:n].reshape(g.shape)
    new_residual = x - local_dq
    # int16 accumulator: exact for ≤256 shards (127·256 < 2^15); 2-byte wire.
    q_sum = jax.lax.psum(q.astype(jnp.int16), axis_name)
    g_sum = (q_sum.astype(jnp.float32) * scale).reshape(-1)[:n].reshape(g.shape)
    return g_sum / n_shards, new_residual


def init_residuals(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
