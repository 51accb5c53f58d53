"""The Qwen2 family (``"architectures": ["Qwen2ForCausalLM"]``): a decoder
of RMSNorm, rotate-half RoPE, grouped-query attention with q/k/v bias and
a SwiGLU MLP, the same layer repeated (arXiv:2407.10671).

A family module gives the harness what it needs of one architecture
(`bench/benchkit/spec.py` finds it by the configuration's own
``architectures[0]``):

    spec(cfg)                  the sizes, from the published keys
    leaves(spec)               path -> `Leaf`, laid out as the program's
    program_config(cfg)        the program's `ModelConfig`; raises, naming
                               the key, for what it cannot state
    call_cost(spec, call)      FLOPs and HBM bytes of one `record.Call`
    served_gaps(spec, seed, samples, control)
                               the plain reference, f32 at ``HIGHEST``
"""

from __future__ import annotations

import dataclasses
import inspect
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchkit.model import BF16_BYTES, Leaf, layer_leaf, seed_key
from benchkit.reference import HI, final, mm, pad_sample, rms, rope


@dataclass(frozen=True)
class ModelSpec:
    """A Qwen2-style decoder, sized by its published `config.json` keys."""

    name: str
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    layers: int
    rope_theta: float
    rms_eps: float
    tied: bool

    @classmethod
    def from_config(cls, cfg: dict) -> "ModelSpec":
        h, n = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
        return cls(
            name=cfg["name"], hidden=h, heads=n,
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim", h // n)),
            ffn=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
            layers=int(cfg["num_hidden_layers"]),
            rope_theta=float(cfg["rope_theta"]),
            rms_eps=float(cfg["rms_norm_eps"]),
            tied=bool(cfg["tie_word_embeddings"]))

    # ------------------------------------------------------------ sizes --

    def layer_matmul_params(self) -> int:
        d, hd = self.hidden, self.head_dim
        attn = d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d
        return attn + 3 * d * self.ffn

    def layer_params(self) -> int:
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim
        return self.layer_matmul_params() + bias + 2 * self.hidden

    def params(self) -> int:
        emb = self.vocab * self.hidden
        return (self.layers * self.layer_params() + self.hidden
                + emb * (1 if self.tied else 2))

    def kv_bytes_per_token(self) -> int:
        """K and V of one token in every layer, bf16."""
        return self.layers * 2 * self.kv_heads * self.head_dim * BF16_BYTES


def spec(cfg: dict) -> ModelSpec:
    return ModelSpec.from_config(cfg)


def call_cost(spec: ModelSpec, call) -> tuple[float, float]:
    """FLOPs and HBM bytes that one decode call needs, whatever implements
    it.  ``call.contexts`` holds, for each row that writes a live entry,
    the number of earlier tokens of its sequence (its position).

    Bytes: every weight read once (the LM head once; an untied embedding
    only as the rows gathered), each live row's earlier K/V entries read
    once, its new entry written once, and its f32 logits written.  Rows
    that take no part in the call need nothing.  FLOPs: 2 per multiply-add
    of every matmul (layers and head) for each live row, plus QK^T and PV
    over its context including the new token."""
    ctx = np.asarray(call.contexts, np.int64)
    rows = len(ctx)
    if rows == 0:
        return 0.0, 0.0
    d = spec.hidden
    head = spec.vocab * d
    weights = (spec.layers * spec.layer_params() + d + head) * BF16_BYTES
    if not spec.tied:
        weights += rows * d * BF16_BYTES  # embedding rows gathered
    kv = spec.kv_bytes_per_token()
    nbytes = weights + kv * (int(ctx.sum()) + rows) + rows * spec.vocab * 4
    per_row = 2 * (spec.layers * spec.layer_matmul_params() + head)
    attn = 4 * spec.layers * spec.heads * spec.head_dim * (ctx + 1)
    flops = rows * per_row + int(attn.sum())
    return float(flops), float(nbytes)


def program_config(cfg: dict):
    """The program's `ModelConfig` for this configuration file: the
    registry entry named by ``program_arch`` with every published size
    taken from the file.  Raises where the registry entry is not the
    architecture the file describes, or where a published key holds a
    value the program cannot state (naming the key), rather than serve
    another model under the file's name."""
    from repro.configs.registry import get_config
    from repro.models.layers import rms_norm

    base = get_config(cfg["program_arch"])
    spec = ModelSpec.from_config(cfg)
    plain = (len(base.unit) == 1 and base.unit[0].kind == "attn"
             and base.unit[0].window == 0 and not base.prefix
             and not base.suffix and base.mlp_kind == "swiglu"
             and base.qkv_bias and not base.norm_plus_one
             and not base.embed_scale and not base.sandwich_norms)
    if not plain:
        raise ValueError(f"{cfg['program_arch']} is not a Qwen2-style stack")
    # the program's every RMSNorm takes `rms_norm`'s default epsilon, and
    # `ModelConfig` has no field for another
    eps = inspect.signature(rms_norm).parameters["eps"].default
    stated = {"rms_norm_eps": (spec.rms_eps, eps),
              "hidden_act": (cfg.get("hidden_act", "silu"), "silu"),
              "use_sliding_window": (bool(cfg.get("use_sliding_window")),
                                     False),
              "rope_scaling": (cfg.get("rope_scaling"), None)}
    for key, (published, program) in stated.items():
        if published != program:
            raise ValueError(
                f"{cfg['name']}: published {key} = {published!r}, but the "
                f"program can only state {program!r}")
    return dataclasses.replace(
        base, d_model=spec.hidden, n_heads=spec.heads,
        n_kv_heads=spec.kv_heads, head_dim=spec.head_dim, d_ff=spec.ffn,
        vocab=spec.vocab, num_units=spec.layers, rope_theta=spec.rope_theta,
        tie_embeddings=spec.tied)


def leaves(spec: ModelSpec) -> dict[str, Leaf]:
    """Norms 1 + 0.1 N, q/k/v biases 0.1 N, matrices N(0, 1/fan_in); the
    layers stacked under ``units/b0`` as the program's one-block unit."""
    d, H, KV, hd, F, V = (spec.hidden, spec.heads, spec.kv_heads,
                          spec.head_dim, spec.ffn, spec.vocab)
    out = {
        "embed": Leaf((V, d), "matrix", d),
        "final_ln": Leaf((d,), "norm"),
    }
    if not spec.tied:
        out["head"] = Leaf((d, V), "matrix", d)
    layer = {
        "units/b0/ln1": Leaf((d,), "norm"),
        "units/b0/ln2": Leaf((d,), "norm"),
        "units/b0/attn/wq": Leaf((d, H, hd), "matrix", d),
        "units/b0/attn/wk": Leaf((d, KV, hd), "matrix", d),
        "units/b0/attn/wv": Leaf((d, KV, hd), "matrix", d),
        "units/b0/attn/wo": Leaf((H, hd, d), "matrix", H * hd),
        "units/b0/attn/bq": Leaf((H, hd), "bias"),
        "units/b0/attn/bk": Leaf((KV, hd), "bias"),
        "units/b0/attn/bv": Leaf((KV, hd), "bias"),
        "units/b0/mlp/wi": Leaf((d, F), "matrix", d),
        "units/b0/mlp/wg": Leaf((d, F), "matrix", d),
        "units/b0/mlp/wo": Leaf((F, d), "matrix", F),
    }
    out.update({k: dataclasses.replace(v, stacked=spec.layers)
                for k, v in layer.items()})
    return out


# ----------------------------------------------------------- reference ----


def _layer(spec: ModelSpec, quant: bool, x, W):
    """One decoder layer over a whole sequence ``x`` (L, d)."""
    L, d = x.shape
    H, KV, hd = spec.heads, spec.kv_heads, spec.head_dim
    h = rms(x, W["ln1"], spec.rms_eps)
    q = mm(h, W["wq"].reshape(d, H * hd), quant).reshape(L, H, hd) + W["bq"]
    k = mm(h, W["wk"].reshape(d, KV * hd), quant).reshape(L, KV, hd) + W["bk"]
    v = mm(h, W["wv"].reshape(d, KV * hd), quant).reshape(L, KV, hd) + W["bv"]
    q, k = rope(q, spec.rope_theta), rope(k, spec.rope_theta)
    g = H // KV
    qg = q.reshape(L, KV, g, hd)
    s = jnp.einsum("qkgd,ckd->kgqc", qg, k, precision=HI) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((L, L), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("kgqc,ckd->qkgd", p, v, precision=HI).reshape(L, H * hd)
    x = x + mm(ctx, W["wo"].reshape(H * hd, d), quant)
    h = rms(x, W["ln2"], spec.rms_eps)
    ff = jax.nn.silu(mm(h, W["wg"], quant)) * mm(h, W["wi"], quant)
    return x + mm(ff, W["wo2"], quant)


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
    those of the int8 control's tokens (None without ``control``)."""
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
    gaps = jax.jit(partial(final, spec.rms_eps))

    embed = gen(key, "embed")
    xs, xc, meta = [], [], []
    for prompt, served in samples:
        ids, pos, toks, n = pad_sample(prompt, served)
        xs.append(embed[ids])
        xc.append(embed[ids] if control else None)
        meta.append((pos, toks, n))
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
        g, cg = gaps(x, c, norm, head, pos, toks)
        served.append(np.asarray(g)[:n])
        if control:
            ctl.append(np.asarray(cg)[:n])
    return (np.concatenate(served),
            np.concatenate(ctl) if control else None)
