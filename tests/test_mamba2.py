"""The Mamba-2 mixer and the Granite 4.0-H hybrid on the served path.

The chunked SSD scan against the token-by-token recurrence, the state
handed from call to call and left alone by padding, and `ModelServer`
(chunked prefill, then decode, through the engine) against the plain f32
reference of the whole model (`models.reference`).

Tolerances.  Everything here runs in float32 on the CPU; the chunked form
sums the same terms in another order, so it meets the recurrence to a few
f32 ulps of the values (about 1e-6 relative).  A state carried in bf16
(8 bits of mantissa) would be off by about 4e-3 relative after one step,
and a state dropped at a chunk boundary by the whole of its carried part:
the weights keep the state for about a hundred tokens (dt ≈ 0.01, A ≈ −1),
so a dropped state shows at every later position.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.models import mamba2
from repro.models.cache import make_mamba2_state
from repro.models.reference import forward_ref
from repro.models.transformer import init_params

CFG = get_smoke_config("granite-4.0-h-micro")


def _recurrence(x, dt, A, B, C, h0):
    """The published recurrence in float64, one step at a time."""
    x, dt, A, B, C, h = (np.asarray(a, np.float64) for a in (x, dt, A, B, C, h0))
    ys = []
    for t in range(x.shape[1]):
        h = (h * np.exp(dt[:, t] * A)[..., None, None]
             + (dt[:, t, :, None] * x[:, t])[..., None] * B[:, t, None, None, :])
        ys.append(np.einsum("bhpn,bn->bhp", h, C[:, t]))
    return np.stack(ys, 1), h


def _ssd_inputs(seed, S, b=2, H=3, P=4, N=5):
    """Long memory: dt ≈ 0.01 and A ≈ −1 decay a state by about 1% a step."""
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(0.01 * np.exp(0.1 * r.standard_normal((b, S, H))), jnp.float32)
    A = -jnp.exp(0.1 * f(H))
    return f(b, S, H, P), dt, A, f(b, S, N), f(b, S, N), f(b, H, P, N)


@pytest.mark.parametrize("S,chunk", [(40, 8), (37, 8), (5, 8), (64, 64)])
def test_chunked_scan_equals_the_recurrence(S, chunk):
    x, dt, A, B, C, h0 = _ssd_inputs(S, S)
    y, h = mamba2.ssd_scan(x, dt, A, B, C, h0, chunk)
    y_ref, h_ref = _recurrence(x, dt, A, B, C, h0)
    # f32 against f64: a few ulps of values of order 1-10
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), h_ref, rtol=1e-5, atol=1e-5)
    if S > chunk:
        # the state matters here: each chunk alone, from h0, misses by far
        # more than the tolerance
        alone = [mamba2.ssd_scan(x[:, c:c + chunk], dt[:, c:c + chunk], A,
                                 B[:, c:c + chunk], C[:, c:c + chunk], h0, chunk)[0]
                 for c in range(0, S, chunk)]
        assert np.abs(np.concatenate(alone, 1) - y_ref).max() > 0.1


def _long_memory(params):
    """The mixers' dt ≈ 0.01 and A ≈ −1 (see the module docstring)."""
    inv = float(np.log(np.expm1(0.01)))  # softplus⁻¹(0.01)

    def fix(path, a):
        name = jax.tree_util.keystr(path)
        if "dt_bias" in name:
            return jnp.full_like(a, inv)
        if "A_log" in name:
            return jnp.zeros_like(a)
        if "in_proj" in name:  # the dt columns small, so dt stays near 0.01
            return a.at[..., -CFG.mamba_heads:].multiply(0.01)
        return a

    return jax.tree_util.tree_map_with_path(fix, params)


def _mixer(seed=0):
    p = init_params(jax.random.PRNGKey(seed), CFG)
    return _long_memory(p)["units"]["b0"]["mixer"]


@partial(jax.jit, static_argnames="decode")
def _run(p, x, pos, st, decode=False):
    layer = jax.tree.map(lambda a: a[0], p)
    return mamba2.mamba2_forward(layer, CFG, x, pos, st, decode)


def _state(b=1):
    return make_mamba2_state(b, CFG.mamba_heads, CFG.mamba_head_dim, CFG.mamba_state,
                             CFG.mamba_conv_dim, CFG.mamba_conv)


def test_the_state_carries_from_call_to_call():
    """A sequence in one call, in calls split across chunk boundaries (the
    conv tail and the SSM state handed on), and token by token in decode
    calls give the same outputs and the same final state."""
    p = _mixer()
    S = 29
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, CFG.d_model))
    pos = jnp.arange(S)[None]
    y, st = _run(p, x, pos, _state())
    for cuts in ([3, 11, 20], [8, 16, 24], [1, 2]):
        ys, s = [], _state()
        for a, b in zip([0] + cuts, cuts + [S]):
            out, s = _run(p, x[:, a:b], pos[:, a:b], s)
            ys.append(out)
        np.testing.assert_allclose(np.concatenate(ys, 1), y, rtol=1e-5, atol=1e-5)
        for k in st:
            np.testing.assert_allclose(s[k], st[k], rtol=1e-5, atol=1e-5)
    ys, s = [], _state()
    for t in range(S):
        out, s = _run(p, x[:, t:t + 1], pos[:, t:t + 1], s, decode=True)
        ys.append(out)
    np.testing.assert_allclose(np.concatenate(ys, 1), y, rtol=1e-5, atol=1e-5)
    for k in st:
        np.testing.assert_allclose(s[k], st[k], rtol=1e-5, atol=1e-5)


def test_padding_leaves_the_state_unchanged():
    p = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, CFG.d_model))
    _, s0 = _run(p, x[:, :5], jnp.arange(5)[None], _state())
    # a chunk whose last positions are padding ends where its valid part ends
    pos = jnp.where(jnp.arange(16) < 7, 5 + jnp.arange(16), -1)[None]
    _, padded = _run(p, x, pos, s0)
    _, valid = _run(p, x[:, :7], pos[:, :7], s0)
    for k in s0:
        np.testing.assert_allclose(padded[k], valid[k], rtol=1e-5, atol=1e-5)
    # a chunk of padding alone, and a decode call at position -1, leave
    # every bit of the state
    _, same = _run(p, x, jnp.full((1, 16), -1), s0)
    _, dec = _run(p, x[:, :1], jnp.full((1, 1), -1), s0, decode=True)
    for k in s0:
        np.testing.assert_array_equal(same[k], s0[k])
        np.testing.assert_array_equal(dec[k], s0[k])
    # beside a live row, in one decode call
    two = jax.tree.map(lambda a: jnp.concatenate([a, a]), s0)
    _, out = _run(p, jnp.concatenate([x[:, :1]] * 2), jnp.asarray([[5], [-1]]), two,
                  decode=True)
    for k in s0:
        np.testing.assert_array_equal(out[k][1], s0[k][0])
        assert not np.array_equal(out[k][0], s0[k][0])


def test_the_served_hybrid_matches_the_reference():
    """Granite-style hybrid (Mamba-2 and attention, reduced widths, f32)
    through `ContinuousBatchingEngine.step` over `ModelServer`: prompts of
    up to two 256-token chunk calls, then decode, on two rows that are
    reset and reused while the other row decodes; every step's logits
    against the whole-sequence f32 reference of the same tokens."""
    from repro.launch.serve import ModelServer
    from repro.serving.scheduler import ContinuousBatchingEngine, Request

    server = ModelServer(CFG, 2, max_len=1024)
    assert server.chunk == 256
    server.params = _long_memory(server.params)
    calls = []
    chunk_call = server._chunk_call
    server._chunk_call = lambda *a: calls.append(1) or chunk_call(*a)
    got = {}

    def step_fn(active):
        out = server.step_fn(active)
        for r, lg in zip(active, out):
            got.setdefault(r.rid, []).append(np.array(lg))
        return out

    eng = ContinuousBatchingEngine(step_fn, server.prefill_request, 2)
    rng = np.random.default_rng(0)
    lens, news = (300, 9, 40, 257), (4, 6, 3, 5)
    reqs = [Request(rid=i, prompt=[int(t) for t in rng.integers(1, CFG.vocab, n)],
                    max_new_tokens=m) for i, (n, m) in enumerate(zip(lens, news))]
    eng.submit_batch(reqs)
    tok = 7
    while eng.stats.finished < len(reqs):
        eng.step(lambda lg: np.full(len(lg), tok))
    assert len(calls) == sum(-(-n // 256) for n in lens)  # no per-token replay
    assert len({r.slot for r in reqs}) == 2
    ref = jax.jit(lambda t: forward_ref(server.params, CFG, t))
    for r in reqs:
        want = np.asarray(ref(jnp.asarray(r.prompt + [tok] * (r.max_new_tokens - 1))))
        want = want[len(r.prompt) - 1:]
        # f32 program (chunked scan, bf16-free) against the f32 recurrence:
        # about 1e-6 relative; a bf16 state would miss by ~1e-3
        np.testing.assert_allclose(np.stack(got[r.rid]), want, rtol=2e-5, atol=2e-5,
                                   err_msg=f"request {r.rid}")


def test_granite_scalars_reach_the_logits():
    """Each of Granite's scalars changes the served logits as the reference
    says it should (so none is silently dropped on the served path)."""
    from repro.models.transformer import decode_step, init_caches, prefill

    m, a = CFG.unit[0], CFG.unit[5]  # mamba2, attn
    base = dataclasses.replace(CFG, unit=(m, a, m), num_units=1)
    params = init_params(jax.random.PRNGKey(3), base)
    toks = jnp.asarray([[3, 9, 27, 81, 243, 5]])
    base_ref = jax.jit(lambda p: forward_ref(p, base, toks[0]))(params)
    for change in ({}, {"embed_mult": 1.0}, {"residual_mult": 1.0}, {"logits_div": 1.0},
                   {"attn_scale": 0.0}, {"rms_eps": 1e-6}, {"rope": True}):
        cfg = dataclasses.replace(base, **change)

        @jax.jit
        def served(params, cfg=cfg):
            caches = init_caches(cfg, 1, 16, jnp.float32)
            lg, caches = prefill(params, cfg, {"tokens": toks[:, :5]}, caches)
            return lg, decode_step(params, cfg, toks[:, 5:], jnp.asarray([[5]]), caches)[0]

        lg, dec = served(params)
        ref = jax.jit(lambda p, cfg=cfg: forward_ref(p, cfg, toks[0]))(params)
        np.testing.assert_allclose(lg[0], ref[4], rtol=2e-5, atol=2e-5, err_msg=str(change))
        np.testing.assert_allclose(dec[0], ref[5], rtol=2e-5, atol=2e-5, err_msg=str(change))
        if change and "rms_eps" not in change:  # eps moves logits by ~1e-5 only
            assert np.abs(ref - base_ref).max() > 1e-4, change  # 5x the tolerance
