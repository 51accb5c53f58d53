"""Per-architecture smoke tests (assignment: every arch instantiates a
REDUCED same-family config and runs forward/train + serve steps on CPU with
shape and finiteness asserts)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config, shapes_for
from repro.models.transformer import (
    decode_step,
    init_caches,
    init_params,
    prefill,
    train_loss,
)


def _batch_for(cfg, B, S):
    batch = {"tokens": jnp.ones((B, S), jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jnp.ones((B, cfg.n_patches, cfg.d_model), jnp.float32)
        batch["labels"] = jnp.ones((B, S + cfg.n_patches), jnp.int32)
    elif cfg.frontend == "audio":
        batch = {"frame_embeds": jnp.ones((B, S, cfg.d_model), jnp.float32),
                 "labels": jnp.ones((B, S), jnp.int32)}
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the assigned dimensions verbatim."""
    cfg = get_config(arch)
    expected = {
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
        "codeqwen1.5-7b": (32, 4096, 32, 32, 13440, 92416),
        "gemma3-1b": (26, 1152, 4, 1, 6912, 262144),
        "xlstm-350m": (24, 1024, None, None, None, 50304),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "deepseek-moe-16b": (28, 2048, 16, 16, None, 102400),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, None, 49155),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
        "granite-4.0-h-micro": (40, 2048, 32, 8, 8192, 100352),
    }[arch]
    L, d, H, KV, dff, V = expected
    assert cfg.n_layers == L
    assert cfg.d_model == d
    assert cfg.vocab == V
    if H is not None:
        assert cfg.n_heads == H and cfg.n_kv_heads == KV
    if dff is not None:
        assert cfg.d_ff == dff
    if arch == "deepseek-moe-16b":
        assert cfg.n_experts == 64 and cfg.top_k == 6 and cfg.n_shared == 2
        assert cfg.d_expert == 1408
    if arch == "granite-moe-3b-a800m":
        assert cfg.n_experts == 40 and cfg.top_k == 8 and cfg.d_expert == 512
    if arch == "granite-4.0-h-micro":
        assert [i for i, b in enumerate(cfg.blocks) if b.kind == "attn"] == [5, 15, 25, 35]
        assert sum(b.kind == "mamba2" for b in cfg.blocks) == 36
        assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state) == (64, 64, 128)
        assert cfg.param_count() + cfg.d_model == 3_191_396_096  # + the final norm


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    """Reduced config: one fwd/train step, output shapes + no NaNs."""
    cfg = get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 32
    batch = _batch_for(cfg, B, S)
    loss, metrics = jax.jit(lambda p, b: train_loss(p, cfg, b))(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: NaN loss"
    assert float(metrics["tokens"]) > 0
    # one SGD-flavoured step decreases loss on a repeated batch (some step
    # size must work — recurrent cells have touchier curvature)
    grads = jax.grad(lambda p: train_loss(p, cfg, batch)[0])(params)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in jax.tree.leaves(grads)), arch
    improved = False
    for lr in (0.5, 0.1, 0.02):
        params2 = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        loss2, _ = train_loss(params2, cfg, batch)
        if float(loss2) < float(loss):
            improved = True
            break
    assert improved, f"{arch}: not trainable at any probe step size"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode_consistency(arch):
    """Prefill+decode(t) == prefill over the longer prefix (cache exactness),
    token by token for 3 steps."""
    import dataclasses

    cfg = get_smoke_config(arch)
    if cfg.n_experts:
        # capacity-based MoE admission is batch-size dependent by design
        # (FCFS overflow); for the exactness check give it headroom so no
        # token drops in either path.
        cfg = dataclasses.replace(cfg, capacity_factor=16.0)
    params = init_params(jax.random.PRNGKey(1), cfg)
    B, S, extra = 2, 16, 3
    key = jax.random.PRNGKey(2)
    toks = jax.random.randint(key, (B, S + extra), 0, cfg.vocab)
    patch = cfg.n_patches if cfg.frontend == "vision" else 0

    def batch_prefix(n):
        b = _batch_for(cfg, B, n)
        if cfg.frontend == "audio":
            emb = jnp.zeros((B, n, cfg.d_model), jnp.float32)
            emb = emb.at[..., 0].set(toks[:, :n].astype(jnp.float32) / cfg.vocab)
            b["frame_embeds"] = emb
        else:
            b["tokens"] = toks[:, :n]
        return b

    if cfg.frontend == "audio":
        # decode over audio tokens uses the embed table — compare decode
        # against itself for determinism instead of prefill equality
        caches = init_caches(cfg, B, S + extra + patch, jnp.float32)
        logits, caches = prefill(params, cfg, batch_prefix(S), caches)
        pos = jnp.full((B, 1), S, jnp.int32)
        l1, c1 = decode_step(params, cfg, toks[:, S:S + 1], pos, caches)
        l2, _ = decode_step(params, cfg, toks[:, S:S + 1], pos, caches)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2))
        assert np.all(np.isfinite(np.asarray(l1)))
        return

    caches = init_caches(cfg, B, S + extra + patch, jnp.float32)
    logits, caches = prefill(params, cfg, batch_prefix(S), caches)
    for t in range(extra):
        pos = jnp.full((B, 1), S + t + patch, jnp.int32)
        logits_dec, caches = decode_step(params, cfg, toks[:, S + t:S + t + 1], pos, caches)
        caches_ref = init_caches(cfg, B, S + extra + patch, jnp.float32)
        logits_ref, _ = prefill(params, cfg, batch_prefix(S + t + 1), caches_ref)
        np.testing.assert_allclose(
            np.asarray(logits_dec), np.asarray(logits_ref), atol=2e-3, rtol=2e-3,
        )


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_shape_assignment(arch):
    shapes = [s.name for s in shapes_for(arch)]
    assert {"train_4k", "prefill_32k", "decode_32k"} <= set(shapes)
    subq = arch in ("xlstm-350m", "recurrentgemma-9b", "gemma3-1b")
    assert ("long_500k" in shapes) == subq
