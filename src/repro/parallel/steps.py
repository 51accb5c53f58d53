"""Train step factory: a jit-ready train step with the distribution
features wired in:

  * gradient accumulation via lax.scan over microbatches (activation memory
    control for the 16 GB/v5e budget; XLA overlaps each microbatch's
    gradient reduce with the next microbatch's compute);
  * configurable remat ('full' recompute per repeated unit for deep/wide
    models, 'dots' selective policy for small ones);
  * FSDP(+TP) parameter sharding and ZeRO'd optimizer state (specs from
    parallel/sharding.py);
  * optional hierarchical int8 error-feedback gradient compression across
    the *pod* axis (shard_map manual over "pod", auto over data/model —
    intra-pod reduction stays fp32 on fast ICI, inter-pod crosses DCI
    quantized; see optim/compression.py).

Shape-specific rule overrides (`_mode_rules`) keep one model code path for
every (arch × shape) pair.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..configs.registry import ShapeSpec
from ..models.transformer import init_params, train_loss
from ..optim.adamw import AdamWConfig, OptState, adamw_update, init_opt_state
from ..optim.compression import compress_psum, init_residuals
from .sharding import (
    axis_rules,
    constrain,
    constrain_tree,
    mesh_axes,
    param_pspecs,
    spec_for,
)


class StepConfig(NamedTuple):
    accum_steps: int = 1
    remat: str = "full"  # "full" | "dots" | "none"
    param_dtype: Any = jnp.bfloat16
    fsdp: bool = True
    compress_pods: bool = False
    act_budget_bytes: float = 6e9
    kv_block: int = 1024  # flash-attention KV block (train/prefill)
    ce_chunk: int = 512  # chunked-CE sequence chunk


# --------------------------------------------------------------- helpers ----


def est_train_act_bytes(cfg: ModelConfig, tokens_micro: float, tp: int) -> float:
    """Rough per-chip activation bytes for one microbatch under 'full' remat:
    scan carries (never model-sharded) + the TP-sharded transient working set
    of one rematerialized unit (qkv/ffn/moe buffers, fp32 attention acc)."""
    div = lambda n: n / tp if (n and n % tp == 0) else n
    D, hd = cfg.d_model, cfg.hd
    heads_eff = div(cfg.n_heads) * hd
    carries = cfg.n_layers * tokens_micro * D * 2
    trans = tokens_micro * 2 * (4 * D + 6 * heads_eff)
    trans += tokens_micro * 4 * 2 * heads_eff  # fp32 online-softmax acc+stats
    if cfg.d_ff:
        trans += tokens_micro * 2 * 3 * div(cfg.d_ff)
    if cfg.n_experts:
        ep = max(cfg.n_experts, cfg.n_experts_pad)
        e_div = tp if ep % tp == 0 else 1
        trans += tokens_micro * cfg.top_k * cfg.capacity_factor * 2 * (
            2 * D + 2 * cfg.d_expert
        ) / e_div
        if cfg.d_shared:
            trans += tokens_micro * 2 * 2 * cfg.d_shared
    if cfg.lru_width:
        trans += tokens_micro * 2 * 8 * div(cfg.lru_width)
    return carries + trans


def default_step_config(cfg: ModelConfig, shape: ShapeSpec, dp: int, **over) -> StepConfig:
    """Pick accumulation and remat for the v5e 16 GB budget."""
    sc = StepConfig()
    if shape.kind == "train":
        axes = mesh_axes()
        tp = axes.get("model", 1)
        per_chip_tokens = shape.global_batch * shape.seq_len / max(dp, 1)
        max_accum = max(1, shape.global_batch // max(dp, 1))
        accum = 1
        while (accum < max_accum
               and est_train_act_bytes(cfg, per_chip_tokens / accum, tp) > sc.act_budget_bytes):
            accum *= 2
        sc = sc._replace(accum_steps=accum, remat="full")
    else:
        sc = sc._replace(fsdp=False, accum_steps=1, remat="none")
    return sc._replace(**over)


def _mode_rules(cfg: ModelConfig, shape: ShapeSpec):
    """Logical-rule overrides per shape: batch axes must divide global_batch."""
    axes = mesh_axes()
    prod = 1
    picked = []
    for a in ("pod", "data"):
        if a in axes and shape.global_batch % (prod * axes[a]) == 0:
            picked.append(a)
            prod *= axes[a]
    return dict(batch=tuple(picked) if picked else None, seq_kv=("model",))


# ---------------------------------------------------------- train step ------


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    residuals: Any  # compression error-feedback (None-like empty dict if off)


def make_train_state(key, cfg: ModelConfig, sc: StepConfig) -> TrainState:
    params = init_params(key, cfg, sc.param_dtype)
    opt = init_opt_state(params)
    res = init_residuals(params) if sc.compress_pods else {}
    return TrainState(params, opt, res)


def train_state_pspecs(state: TrainState, sc: StepConfig):
    pspec = param_pspecs(state.params, fsdp=sc.fsdp)
    res_spec = param_pspecs(state.residuals, fsdp=sc.fsdp) if state.residuals else {}
    return TrainState(
        params=pspec,
        opt=OptState(step=spec_for(), mu=pspec, nu=pspec, master=pspec),
        residuals=res_spec,
    )


def make_train_step(cfg: ModelConfig, shape: ShapeSpec, sc: StepConfig,
                    opt_cfg: AdamWConfig = AdamWConfig()):
    """Returns train_step(state, batch) → (state', metrics). Jit/pjit-ready;
    call under an active mesh (jax.set_mesh) or on a single device."""
    rules = _mode_rules(cfg, shape)
    A = sc.accum_steps

    def loss_fn(params, mb):
        return train_loss(params, cfg, mb, remat=sc.remat, kv_block=sc.kv_block,
                          ce_chunk=sc.ce_chunk)

    def grads_and_metrics(params, batch):
        """Microbatch-accumulated fp32 grads (scan when A > 1)."""
        if A == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            return grads, dict(metrics, loss=loss)

        split = lambda x: x.reshape((A, x.shape[0] // A) + x.shape[1:])
        batch_r = jax.tree.map(split, batch)

        def micro(carry, mb):
            gacc, lacc, macc = carry
            mb = jax.tree.map(lambda x: constrain(x, "batch"), mb)
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            gacc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), gacc, grads)
            macc = jax.tree.map(lambda a, b: a + b, macc, metrics)
            return (gacc, lacc + loss, macc), None

        gacc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        m0 = {k: jnp.zeros((), jnp.float32) for k in
              ("ce", "lb_loss", "router_z", "overflow_frac", "tokens")}
        (gacc, loss, macc), _ = jax.lax.scan(micro, (gacc0, jnp.float32(0), m0), batch_r)
        grads = jax.tree.map(lambda g: g / A, gacc)
        metrics = {k: v / A for k, v in macc.items()}
        metrics["tokens"] = macc["tokens"]
        return grads, dict(metrics, loss=loss / A)

    def apply_updates(state: TrainState, grads, metrics):
        new_params, new_opt, opt_m = adamw_update(opt_cfg, grads, state.opt, sc.param_dtype)
        metrics.update(opt_m)
        return new_params, new_opt, metrics

    if not sc.compress_pods:

        def train_step(state: TrainState, batch):
            with axis_rules(**rules):
                grads, metrics = grads_and_metrics(state.params, batch)
                new_params, new_opt, metrics = apply_updates(state, grads, metrics)
                if mesh_axes():
                    specs = train_state_pspecs(state, sc)
                    new_params = constrain_tree(new_params, specs.params)
                return TrainState(new_params, new_opt, state.residuals), metrics

        return train_step

    # ---- hierarchical compressed variant: manual over "pod", auto inside ----
    def train_step_compressed(state: TrainState, batch):
        axes = mesh_axes()
        n_pods = axes.get("pod", 1)
        mesh = jax.sharding.get_abstract_mesh()
        with axis_rules(**rules):
            # grads within each pod: data+model handled automatically (auto
            # axes), pod manual. Batch enters split over pod (dim 0).
            pspec = train_state_pspecs(state, sc).params

            def per_pod(params, pod_batch):
                grads, metrics = grads_and_metrics(params, pod_batch)
                return grads, metrics

            in_batch_specs = jax.tree.map(lambda _: jax.sharding.PartitionSpec("pod"), batch)
            rep = jax.tree.map(lambda _: jax.sharding.PartitionSpec(), state.params)

            def body(params, pod_batch, residuals):
                grads, metrics = per_pod(params, pod_batch)
                # hierarchical exchange: fp32 within pod already done by auto
                # sharding; across pods → int8-range EF compression.
                if n_pods > 1:
                    flat_g, tdef = jax.tree_util.tree_flatten(grads)
                    flat_r = tdef.flatten_up_to(residuals)
                    out = [compress_psum(g, r, "pod", n_pods) for g, r in zip(flat_g, flat_r)]
                    grads = tdef.unflatten([o[0] for o in out])
                    residuals = tdef.unflatten([o[1] for o in out])
                metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "pod"), metrics)
                return grads, residuals, metrics

            grads, new_res, metrics = jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(rep, in_batch_specs, rep),
                out_specs=(rep, rep, jax.sharding.PartitionSpec()),
                check_vma=False,
                axis_names={"pod"},
            )(state.params, batch, state.residuals)
            new_params, new_opt, metrics = apply_updates(state, grads, metrics)
            return TrainState(new_params, new_opt, new_res), metrics

    return train_step_compressed
