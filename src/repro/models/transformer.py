"""Unified decoder stack over heterogeneous blocks.

A model is ``prefix + unit×num_units + suffix`` of blocks (configs/base.py).
The repeating unit is lax.scan'ed with stacked parameters → HLO size is O(1)
in depth (essential for compiling 80-layer models) and the
scan body is remat'ed (selective activation checkpointing).

Modes (static):
  train    — full-sequence forward, no caches, chunked-CE loss
  prefill  — full-sequence forward, writes KV caches / recurrent states
  decode   — one token per row against the caches
  chunk    — one row's prompt chunk against that row's KV ring and
             recurrent state

Frontend stubs (per assignment): "audio" consumes precomputed frame
embeddings (B,S,D); "vision" prepends precomputed patch embeddings (B,P,D)
to the token embeddings.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..configs.base import Block, ModelConfig
from ..parallel.sharding import constrain
from .cache import (
    kv_cache_append,
    kv_cache_write_chunk,
    kv_cache_write_prefill,
    kv_cache_write_slot,
    make_kv_cache,
    make_mamba2_state,
    make_mlstm_state,
    make_rglru_state,
    make_slstm_state,
    seq_shard_axes,
)
from .layers import (
    MLP_FWD,
    MLP_INIT,
    attn_output,
    blockwise_attention,
    chunk_attention,
    init_attention,
    init_embedding,
    init_rms_norm,
    qkv_project,
    rms_norm,
    single_query_attention,
)
from .mamba2 import init_mamba2, mamba2_forward
from .moe import init_moe, moe_forward
from .rglru import init_rglru_block, rglru_block_forward, rglru_block_step
from .xlstm import init_mlstm, init_slstm, mlstm_forward, slstm_forward

ZERO_AUX = {"lb_loss": 0.0, "z_loss": 0.0, "overflow_frac": 0.0}


def _add_aux(a, b):
    return {k: a[k] + b[k] for k in a}


# ------------------------------------------------------------------ init ----


def init_block(key, spec: Block, cfg: ModelConfig, dtype):
    d = cfg.d_model
    if spec.kind in ("attn", "moe"):
        ks = jax.random.split(key, 2)
        p = {
            "ln1": init_rms_norm(d, dtype, cfg.norm_plus_one),
            "attn": init_attention(
                ks[0],
                dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, qkv_bias=cfg.qkv_bias),
                d,
                dtype,
            ),
            "ln2": init_rms_norm(d, dtype, cfg.norm_plus_one),
        }
        if cfg.sandwich_norms:
            p["ln1b"] = init_rms_norm(d, dtype, cfg.norm_plus_one)
            p["ln2b"] = init_rms_norm(d, dtype, cfg.norm_plus_one)
        if spec.kind == "attn":
            p["mlp"] = MLP_INIT[cfg.mlp_kind](ks[1], d, cfg.d_ff, dtype)
        else:
            p["moe"] = init_moe(
                ks[1], d, cfg.n_experts, cfg.d_expert, cfg.top_k, cfg.n_shared, cfg.d_shared,
                dtype, n_experts_pad=cfg.n_experts_pad,
            )
        return p
    if spec.kind == "mlstm":
        return init_mlstm(key, d, cfg.xlstm_heads, dtype)
    if spec.kind == "slstm":
        return init_slstm(key, d, cfg.xlstm_heads, dtype)
    if spec.kind == "mamba2":
        k1, k2 = jax.random.split(key)
        return {
            "ln1": init_rms_norm(d, dtype),
            "mixer": init_mamba2(k1, d, cfg, dtype),
            "ln2": init_rms_norm(d, dtype),
            "mlp": MLP_INIT[cfg.mlp_kind](k2, d, cfg.d_ff, dtype),
        }
    if spec.kind == "rglru":
        k1, k2 = jax.random.split(key)
        return {
            "temporal": init_rglru_block(k1, d, cfg.lru_width, dtype),
            "ln2": init_rms_norm(d, dtype, cfg.norm_plus_one),
            "mlp": MLP_INIT[cfg.mlp_kind](k2, d, cfg.d_ff, dtype),
        }
    raise ValueError(f"unknown block kind {spec.kind}")


def init_params(key, cfg: ModelConfig, dtype=jnp.float32):
    keys = jax.random.split(key, 6)
    params = {
        "embed": init_embedding(keys[0], cfg.vocab, cfg.d_model, dtype),
        "final_ln": init_rms_norm(cfg.d_model, dtype, cfg.norm_plus_one),
    }
    if not cfg.tie_embeddings:
        params["head"] = (
            jax.random.normal(keys[1], (cfg.d_model, cfg.vocab)) * cfg.d_model**-0.5
        ).astype(dtype)

    def init_unit(k):
        ks = jax.random.split(k, max(1, len(cfg.unit)))
        return {f"b{i}": init_block(ks[i], spec, cfg, dtype) for i, spec in enumerate(cfg.unit)}

    if cfg.num_units > 0:
        params["units"] = jax.vmap(init_unit)(jax.random.split(keys[2], cfg.num_units))
    for name, blocks, k in (("prefix", cfg.prefix, keys[3]), ("suffix", cfg.suffix, keys[4])):
        if blocks:
            ks = jax.random.split(k, len(blocks))
            params[name] = [init_block(ks[i], spec, cfg, dtype) for i, spec in enumerate(blocks)]
    return params


# ---------------------------------------------------------------- caches ----


def init_block_cache(spec: Block, cfg: ModelConfig, batch: int, capacity: int, dtype):
    if spec.kind in ("attn", "moe"):
        cap = min(capacity, spec.window) if spec.window > 0 else capacity
        return make_kv_cache(batch, cap, cfg.n_kv_heads, cfg.hd, dtype)
    if spec.kind == "mlstm":
        d_in = 2 * cfg.d_model
        hd = d_in // cfg.xlstm_heads
        return make_mlstm_state(batch, cfg.xlstm_heads, hd, hd, d_in)
    if spec.kind == "slstm":
        return make_slstm_state(batch, cfg.xlstm_heads, cfg.d_model // cfg.xlstm_heads)
    if spec.kind == "rglru":
        return make_rglru_state(batch, cfg.lru_width)
    if spec.kind == "mamba2":
        return make_mamba2_state(batch, cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state,
                                 cfg.mamba_conv_dim, cfg.mamba_conv)
    raise ValueError(spec.kind)


def init_caches(cfg: ModelConfig, batch: int, capacity: int, dtype):
    caches = {}
    if cfg.num_units > 0:

        def one(spec):
            return init_block_cache(spec, cfg, batch, capacity, dtype)

        unit_cache = {f"b{i}": one(spec) for i, spec in enumerate(cfg.unit)}
        caches["units"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_units,) + x.shape), unit_cache
        )
    for name, blocks in (("prefix", cfg.prefix), ("suffix", cfg.suffix)):
        if blocks:
            caches[name] = [init_block_cache(s, cfg, batch, capacity, dtype) for s in blocks]
    return caches


def _map_block_caches(fn, cfg: ModelConfig, *trees):
    """``fn(spec, batch_axis, *block_caches)`` over every block of caches
    laid out as `init_caches` lays them (the unit stack leads with U)."""
    out = {}
    if cfg.num_units > 0:
        out["units"] = {
            f"b{i}": fn(spec, 1, *(t["units"][f"b{i}"] for t in trees))
            for i, spec in enumerate(cfg.unit)
        }
    for name, blocks in (("prefix", cfg.prefix), ("suffix", cfg.suffix)):
        if blocks:
            out[name] = [fn(s, 0, *cs) for s, *cs in zip(blocks, *(t[name] for t in trees))]
    return out


def reset_cache_row(cfg: ModelConfig, caches, row):
    """``caches`` with batch row ``row`` as `init_caches` made it: KV entries
    empty (position -1) and recurrent states zero.  The KV ring cursors are
    shared by every row and kept."""

    def reset(spec, ax, c):
        at = (slice(None),) * ax + (row,)
        if spec.kind in ("attn", "moe"):
            return {**c, "pos": c["pos"].at[at].set(-1)}
        return jax.tree.map(lambda x: x.at[at].set(0), c)

    return _map_block_caches(reset, cfg, caches)


def split_caches(cfg: ModelConfig, caches):
    """(rings, states): the KV rings of ``caches`` (attention and MoE
    blocks) and the other blocks' recurrent states, each tree with an
    empty dict in the other's place; `join_caches` undoes it."""
    kv = lambda spec: spec.kind in ("attn", "moe")
    return (_map_block_caches(lambda s, ax, c: c if kv(s) else {}, cfg, caches),
            _map_block_caches(lambda s, ax, c: {} if kv(s) else c, cfg, caches))


def join_caches(cfg: ModelConfig, rings, states):
    """The caches that `split_caches` split into ``rings`` and ``states``."""
    return _map_block_caches(lambda s, ax, r, st: r or st, cfg, rings, states)


def keep_cache_rows(cfg: ModelConfig, new, old, live):
    """``new`` (after a decode step from ``old``) with the recurrent states of
    the rows where ``live`` (B,) is False taken back from ``old``.  A decode
    step advances every row's recurrent state; a row at position -1 writes
    only an empty KV entry, so KV caches are taken from ``new`` as they are,
    and a Mamba-2 block leaves such a row's state as it was by itself."""

    def keep(spec, ax, n, o):
        if spec.kind in ("attn", "moe", "mamba2"):
            return n

        def pick(a, b):
            rows = live.reshape((1,) * ax + (-1,) + (1,) * (a.ndim - ax - 1))
            return jnp.where(rows, a, b)

        return jax.tree.map(pick, n, o)

    return _map_block_caches(keep, cfg, new, old)


# --------------------------------------------------------------- forward ----


def block_forward(spec: Block, cfg: ModelConfig, p, x, positions, cache, mode: str,
                  kv_block: int = 1024):
    """Returns (x', new_cache, aux). Residuals are applied inside."""
    aux = dict(ZERO_AUX)
    eps = cfg.rms_eps
    if spec.kind in ("attn", "moe"):
        theta = spec.rope_theta or cfg.rope_theta
        scale = cfg.attn_scale or None
        xn = rms_norm(x, p["ln1"], eps, plus_one=cfg.norm_plus_one)
        q, k, v = qkv_project(p["attn"], xn, positions, theta, rope=cfg.rope)
        q = constrain(q, "batch", "seq", "heads")
        new_cache = cache
        if mode == "train":
            ctx = blockwise_attention(q, k, v, positions, positions, window=spec.window,
                                      kv_block=kv_block, scale=scale)
        elif mode == "prefill":
            ctx = blockwise_attention(q, k, v, positions, positions, window=spec.window,
                                      kv_block=kv_block, scale=scale)
            new_cache = kv_cache_write_prefill(cache, k, v, positions)
        elif mode == "chunk":  # one row's prompt chunk over its ring entries
            # (``cache``: that row's view); the new entries are returned for
            # `prefill_chunk` to write
            ctx = chunk_attention(q, k, v, positions, cache["k"], cache["v"],
                                  cache["pos"], scale=scale)
            new_cache = {"k": k, "v": v}
        elif seq_shard_axes():  # decode over a sequence-sharded ring — dense
            # single-query path (scan-free; the partitioner emits the
            # flash-decode LSE-merge all-reduces) after a one-hot write: a
            # dynamic-update-slice on that axis rematerializes the cache
            new_cache = kv_cache_append(cache, k, v, positions)
            ctx = single_query_attention(
                q, new_cache["k"], new_cache["v"], positions, new_cache["pos"], window=spec.window,
                scale=scale,
            )
        else:  # decode: the ring as it stood beside the new entry, which is
            # returned for `decode_step` to write in place after the scan
            dt = cache["k"].dtype
            new_cache = {"k": k.astype(dt), "v": v.astype(dt), "pos": positions}
            ctx = single_query_attention(
                q, cache["k"], cache["v"], positions, cache["pos"], window=spec.window,
                scale=scale, new=(new_cache["k"], new_cache["v"]),
                skip=cache["ptr"] % cache["k"].shape[1],
            )
        attn_out = attn_output(p["attn"], ctx)
        if cfg.sandwich_norms:
            attn_out = rms_norm(attn_out, p["ln1b"], eps, plus_one=cfg.norm_plus_one)
        x = x + _residual(cfg, attn_out)
        xn2 = rms_norm(x, p["ln2"], eps, plus_one=cfg.norm_plus_one)
        if spec.kind == "attn":
            ff = MLP_FWD[cfg.mlp_kind](p["mlp"], xn2)
        else:
            ff, aux = moe_forward(
                p["moe"], xn2, top_k=cfg.top_k, capacity_factor=cfg.capacity_factor
            )
            aux = dict(ZERO_AUX, **aux)
        if cfg.sandwich_norms:
            ff = rms_norm(ff, p["ln2b"], eps, plus_one=cfg.norm_plus_one)
        return x + _residual(cfg, ff), new_cache, aux
    if spec.kind == "mamba2":
        # ``cache``: the rows' state (in mode "chunk", the one row's view)
        mix, st = mamba2_forward(p["mixer"], cfg, rms_norm(x, p["ln1"], eps), positions,
                                 cache, decode=mode == "decode")
        x = x + _residual(cfg, mix)
        ff = MLP_FWD[cfg.mlp_kind](p["mlp"], rms_norm(x, p["ln2"], eps))
        return x + _residual(cfg, ff), st, aux
    if spec.kind == "mlstm":
        x, st = mlstm_forward(p, x, cfg.xlstm_heads, cache)
        return x, st, aux
    if spec.kind == "slstm":
        x, st = slstm_forward(p, x, cfg.xlstm_heads, cache)
        return x, st, aux
    if spec.kind == "rglru":
        if mode == "decode":
            x, st = rglru_block_step(p["temporal"], x, cache)
        else:
            x, st = rglru_block_forward(p["temporal"], x, cache)
        xn = rms_norm(x, p["ln2"], eps, plus_one=cfg.norm_plus_one)
        return x + MLP_FWD[cfg.mlp_kind](p["mlp"], xn), st, aux
    raise ValueError(spec.kind)


def _residual(cfg: ModelConfig, y):
    """A block's output as it joins the residual stream (× residual_mult)."""
    return y if cfg.residual_mult == 1.0 else y * jnp.asarray(cfg.residual_mult, y.dtype)


def _apply_unit(cfg, unit_params, x, positions, unit_cache, mode, kv_block=1024):
    aux_sum = dict(ZERO_AUX)
    new_caches = {}
    for i, spec in enumerate(cfg.unit):
        cache_i = None if unit_cache is None else unit_cache[f"b{i}"]
        x, nc, aux = block_forward(spec, cfg, unit_params[f"b{i}"], x, positions, cache_i, mode,
                                   kv_block=kv_block)
        new_caches[f"b{i}"] = nc
        aux_sum = _add_aux(aux_sum, aux)
    return x, new_caches, aux_sum


_REMAT_POLICIES = {
    "full": None,  # save only per-unit inputs (max recompute, min memory)
    "dots": "dots_with_no_batch_dims_saveable",
}


def _group_size(u: int) -> int:
    """Largest divisor of u that is ≤ ceil(sqrt(u)) (√L checkpointing)."""
    import math as _m

    target = _m.isqrt(u) + (0 if _m.isqrt(u) ** 2 == u else 1)
    for g in range(target, 0, -1):
        if u % g == 0:
            return g
    return 1


def forward_hidden(params, cfg: ModelConfig, x, positions, caches=None, mode="train",
                   remat="dots", kv_block: int = 1024):
    """x: (B,S,D) input embeddings → (h, new_caches, aux)."""
    aux_total = dict(ZERO_AUX)
    new_caches = {"prefix": [], "suffix": []} if caches is not None else None

    for i, spec in enumerate(cfg.prefix):
        c = caches["prefix"][i] if caches is not None else None
        x, nc, aux = block_forward(spec, cfg, params["prefix"][i], x, positions, c, mode,
                                   kv_block=kv_block)
        aux_total = _add_aux(aux_total, aux)
        if caches is not None:
            new_caches["prefix"].append(nc)

    if cfg.num_units > 0:
        if caches is None:

            def unit_fn(xc, up):
                xo, _, aux = _apply_unit(cfg, up, xc, positions, None, mode, kv_block)
                return xo, aux

            if remat == "2level" and mode == "train":
                # nested (√L) activation checkpointing: scan over unit GROUPS
                # (outer checkpoint saves one carry per group) with a
                # checkpointed per-unit scan inside (backward re-runs one
                # group, then re-runs one unit at a time).  Saved activations
                # drop from U·|x| to (U/g + g)·|x| for ~2 extra fwd passes.
                U = cfg.num_units
                g = _group_size(U)
                inner = jax.checkpoint(unit_fn)

                @jax.checkpoint
                def group_fn(xc, gp):
                    return jax.lax.scan(inner, xc, gp)

                grouped = jax.tree.map(
                    lambda a: a.reshape((U // g, g) + a.shape[1:]), params["units"]
                )
                x, auxs = jax.lax.scan(group_fn, x, grouped)
            else:
                if remat in _REMAT_POLICIES and mode == "train":
                    pol = _REMAT_POLICIES[remat]
                    unit_fn = jax.checkpoint(
                        unit_fn, policy=getattr(jax.checkpoint_policies, pol) if pol else None
                    )
                x, auxs = jax.lax.scan(unit_fn, x, params["units"])
            aux_total = _add_aux(aux_total, jax.tree.map(jnp.sum, auxs))
        else:

            def unit_fn_c(xc, inp):
                up, uc = inp
                xo, ncs, aux = _apply_unit(cfg, up, xc, positions, uc, mode, kv_block)
                return xo, (ncs, aux)

            x, (ncs, auxs) = jax.lax.scan(unit_fn_c, x, (params["units"], caches["units"]))
            new_caches["units"] = ncs
            aux_total = _add_aux(aux_total, jax.tree.map(jnp.sum, auxs))

    for i, spec in enumerate(cfg.suffix):
        c = caches["suffix"][i] if caches is not None else None
        x, nc, aux = block_forward(spec, cfg, params["suffix"][i], x, positions, c, mode,
                                   kv_block=kv_block)
        aux_total = _add_aux(aux_total, aux)
        if caches is not None:
            new_caches["suffix"].append(nc)

    h = rms_norm(x, params["final_ln"], cfg.rms_eps, plus_one=cfg.norm_plus_one)
    if new_caches is not None:
        new_caches = {k: v for k, v in new_caches.items() if v != []}
    return h, new_caches, aux_total


# ------------------------------------------------------------ embeddings ----


def embed_inputs(params, cfg: ModelConfig, batch):
    """Assemble input embeddings + positions + loss mask from a batch dict."""
    if cfg.frontend == "audio":
        x = batch["frame_embeds"]
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        mask = jnp.ones((B, S), jnp.float32)
    elif cfg.frontend == "vision":
        tok = params["embed"][batch["tokens"]]
        x = jnp.concatenate([batch["patch_embeds"].astype(tok.dtype), tok], axis=1)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        P = batch["patch_embeds"].shape[1]
        mask = jnp.concatenate(
            [jnp.zeros((B, P), jnp.float32), jnp.ones((B, S - P), jnp.float32)], axis=1
        )
    else:
        x = params["embed"][batch["tokens"]]
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        mask = jnp.ones((B, S), jnp.float32)
    if "loss_mask" in batch:
        mask = mask * batch["loss_mask"]
    return _scale_embed(cfg, x), positions, mask


def _scale_embed(cfg: ModelConfig, x):
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    if cfg.embed_mult != 1.0:
        x = x * jnp.asarray(cfg.embed_mult, x.dtype)
    return x


def lm_head(params, cfg: ModelConfig):
    return params["embed"].T if cfg.tie_embeddings else params["head"]


def _logits(cfg: ModelConfig, logits):
    """f32 logits, ÷ logits_div."""
    logits = logits.astype(jnp.float32)
    return logits if cfg.logits_div == 1.0 else logits / cfg.logits_div


# ----------------------------------------------------------------- loss ----


def chunked_ce_loss(h, head, labels, mask, chunk: int = 512, z_weight: float = 0.0,
                    logits_div: float = 1.0):
    """Cross-entropy without materializing (B,S,V) logits: scan over sequence
    chunks; fp32 statistics; vocab dim stays sharded (`vocab` → model axis);
    logits ÷ ``logits_div``."""
    B, S, D = h.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    nch = S // chunk

    # checkpointed body: backward recomputes each chunk's logits from h
    # instead of storing (B, S, V) residuals across the scan.
    @jax.checkpoint
    def body(carry, i):
        loss_sum, z_sum, cnt = carry
        hs = jax.lax.dynamic_slice(h, (0, i * chunk, 0), (B, chunk, D))
        lab = jax.lax.dynamic_slice(labels, (0, i * chunk), (B, chunk))
        msk = jax.lax.dynamic_slice(mask, (0, i * chunk), (B, chunk))
        logits = jnp.einsum("bsd,dv->bsv", hs, head.astype(hs.dtype)).astype(jnp.float32)
        if logits_div != 1.0:
            logits = logits / logits_div
        logits = constrain(logits, "batch", "seq", "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        loss_sum = loss_sum + jnp.sum((lse - ll) * msk)
        z_sum = z_sum + jnp.sum(lse**2 * msk)
        return (loss_sum, z_sum, cnt + jnp.sum(msk)), None

    if nch == 1:
        (loss_sum, z_sum, cnt), _ = body(
            (jnp.float32(0), jnp.float32(0), jnp.float32(0)), jnp.int32(0)
        )
    else:
        (loss_sum, z_sum, cnt), _ = jax.lax.scan(
            body, (jnp.float32(0), jnp.float32(0), jnp.float32(0)), jnp.arange(nch)
        )
    cnt = jnp.maximum(cnt, 1.0)
    return loss_sum / cnt + z_weight * z_sum / cnt, cnt


def train_loss(params, cfg: ModelConfig, batch, remat="dots", kv_block: int = 1024,
               ce_chunk: int = 512):
    """Full training objective: chunked CE + MoE aux losses. Returns
    (loss, metrics)."""
    x, positions, mask = embed_inputs(params, cfg, batch)
    h, _, aux = forward_hidden(params, cfg, x, positions, None, "train", remat=remat,
                               kv_block=kv_block)
    ce, cnt = chunked_ce_loss(h, lm_head(params, cfg), batch["labels"], mask,
                              chunk=ce_chunk, z_weight=cfg.z_loss_weight,
                              logits_div=cfg.logits_div)
    n_moe = max(1, sum(1 for b in cfg.blocks if b.kind == "moe"))
    lb = aux["lb_loss"] / n_moe
    loss = ce + 0.01 * lb + aux["z_loss"] / n_moe
    metrics = {
        "ce": ce,
        "lb_loss": lb,
        "router_z": aux["z_loss"] / n_moe,
        "overflow_frac": aux["overflow_frac"] / n_moe,
        "tokens": cnt,
    }
    return loss, metrics


# ---------------------------------------------------------------- serving ---


def prefill(params, cfg: ModelConfig, batch, caches, kv_block: int = 1024):
    """Full-context forward that fills caches; returns (last-pos logits, caches)."""
    x, positions, _ = embed_inputs(params, cfg, batch)
    h, caches, _ = forward_hidden(params, cfg, x, positions, caches, "prefill", remat="none",
                                  kv_block=kv_block)
    logits = jnp.einsum("bd,dv->bv", h[:, -1, :], lm_head(params, cfg).astype(h.dtype))
    return _logits(cfg, logits), caches


def prefill_chunk(params, cfg: ModelConfig, tokens, positions, caches, row):
    """One prompt chunk of batch row ``row`` through a stack of
    full-context attention and Mamba-2 blocks: tokens and positions (T,),
    position -1 marking padding (at the end).  Each attention block's
    queries attend over the row's ring entries and the chunk's own
    (`layers.chunk_attention`); the valid entries then land where as many
    decode calls would put them (`cache.kv_cache_write_chunk`).  Each
    Mamba-2 block scans the chunk from the row's state and writes the
    state after the last valid token back at the row.  Returns (logits
    (V,) f32 of the last valid token, caches)."""
    x = _scale_embed(cfg, params["embed"][tokens[None]])

    def view(spec, ax, c):
        names = ("k", "v", "pos") if spec.kind == "attn" else tuple(c)
        return {n: jax.lax.dynamic_slice_in_dim(c[n], row, 1, ax) for n in names}

    def write(spec, ax, c, n):
        if spec.kind == "attn":
            return kv_cache_write_chunk(c, row, n["k"], n["v"], positions, ax)
        return {k: jax.lax.dynamic_update_slice_in_dim(c[k], n[k].astype(c[k].dtype), row, ax)
                for k in c}

    rows = _map_block_caches(view, cfg, caches)
    h, new, _ = forward_hidden(params, cfg, x, positions[None], rows, "chunk",
                               remat="none")
    last = jnp.sum(positions >= 0) - 1
    h_last = jax.lax.dynamic_index_in_dim(h[0], last, keepdims=False)
    logits = jnp.einsum("d,dv->v", h_last, lm_head(params, cfg).astype(h.dtype))
    caches = _map_block_caches(write, cfg, caches, new)
    return _logits(cfg, logits), caches


def decode_step(params, cfg: ModelConfig, tokens, positions, caches):
    """tokens (B,1) int32, positions (B,1) int32 → (logits (B,V) f32, caches).

    A ring that is not sequence sharded comes back from the layers as its
    new entries alone, and each leaf takes them with one write at the slot
    (`cache.kv_cache_write_slot`), in place where the caches are donated."""
    x = _scale_embed(cfg, params["embed"][tokens])
    h, new, _ = forward_hidden(params, cfg, x, positions, caches, "decode", remat="none")
    if not seq_shard_axes():
        new = _map_block_caches(
            lambda s, ax, c, n: kv_cache_write_slot(c, n, ax) if s.kind in ("attn", "moe") else n,
            cfg, caches, new)
    logits = jnp.einsum("bd,dv->bv", h[:, -1, :], lm_head(params, cfg).astype(h.dtype))
    return _logits(cfg, logits), new


partial  # (linter guard)
