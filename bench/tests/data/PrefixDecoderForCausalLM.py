"""A second family, as a later configuration of another architecture would
bring one: a decoder without q/k/v bias whose first layer stands outside
the repeated unit (the program's ``prefix``), so that its leaves differ by
layer kind and one of them is a list.  `bench/tests` copies it into a
checkout's ``bench/families/`` and runs a tiny cell through it; the
harness under ``bench/benchkit/`` is not touched.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchkit.model import BF16_BYTES, Leaf, layer_leaf, seed_key
from benchkit.reference import HI, final, mm, pad_sample, rms, rope

NAMES = ("ln1", "ln2", "attn/wq", "attn/wk", "attn/wv", "attn/wo", "mlp/wi",
         "mlp/wg", "mlp/wo")


@dataclass(frozen=True)
class Spec:
    name: str
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    layers: int  # the prefix layer and the unit's
    rope_theta: float
    rms_eps: float


def spec(cfg: dict) -> Spec:
    h, n = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return Spec(cfg["name"], h, n, int(cfg["num_key_value_heads"]), h // n,
                int(cfg["intermediate_size"]), int(cfg["vocab_size"]),
                int(cfg["num_hidden_layers"]), float(cfg["rope_theta"]),
                float(cfg["rms_norm_eps"]))


def _layer_leaves(s: Spec, stacked: int) -> dict[str, Leaf]:
    d, H, KV, hd, F = s.hidden, s.heads, s.kv_heads, s.head_dim, s.ffn
    shapes = [((d,), "norm", 1), ((d,), "norm", 1), ((d, H, hd), "matrix", d),
              ((d, KV, hd), "matrix", d), ((d, KV, hd), "matrix", d),
              ((H, hd, d), "matrix", H * hd), ((d, F), "matrix", d),
              ((d, F), "matrix", d), ((F, d), "matrix", F)]
    return {n: Leaf(*x, stacked=stacked) for n, x in zip(NAMES, shapes)}


def leaves(s: Spec) -> dict[str, Leaf]:
    out = {"embed": Leaf((s.vocab, s.hidden), "matrix", s.hidden),
           "final_ln": Leaf((s.hidden,), "norm")}
    out.update({f"prefix/0/{n}": v for n, v in _layer_leaves(s, 0).items()})
    out.update({f"units/b0/{n}": v
                for n, v in _layer_leaves(s, s.layers - 1).items()})
    return out


def program_config(cfg: dict):
    from repro.configs.base import Block
    from repro.configs.registry import get_config

    s = spec(cfg)
    return dataclasses.replace(
        get_config(cfg["program_arch"]), d_model=s.hidden, n_heads=s.heads,
        n_kv_heads=s.kv_heads, head_dim=s.head_dim, d_ff=s.ffn, vocab=s.vocab,
        prefix=(Block("attn"),), unit=(Block("attn"),),
        num_units=s.layers - 1, qkv_bias=False, rope_theta=s.rope_theta,
        tie_embeddings=True)


def call_cost(s: Spec, call) -> tuple[float, float]:
    """Weights once, each live row's K/V read and written, f32 logits;
    2 FLOPs per multiply-add and attention over the context."""
    ctx = np.asarray(call.contexts, np.int64)
    if not len(ctx):
        return 0.0, 0.0
    d, hd = s.hidden, s.head_dim
    mat = (d * (s.heads + 2 * s.kv_heads) * hd + s.heads * hd * d
           + 3 * d * s.ffn)
    weights = (s.layers * (mat + 2 * d) + d + s.vocab * d) * BF16_BYTES
    kv = s.layers * 2 * s.kv_heads * hd * BF16_BYTES
    nbytes = weights + kv * int((ctx + 1).sum()) + len(ctx) * s.vocab * 4
    attn = 4 * s.layers * s.heads * hd * (ctx + 1)
    flops = 2 * len(ctx) * (s.layers * mat + s.vocab * d) + int(attn.sum())
    return float(flops), float(nbytes)


def _layer(s: Spec, quant: bool, x, W):
    L, d = x.shape
    H, KV, hd = s.heads, s.kv_heads, s.head_dim
    h = rms(x, W["ln1"], s.rms_eps)
    q = mm(h, W["attn/wq"].reshape(d, H * hd), quant).reshape(L, H, hd)
    k = mm(h, W["attn/wk"].reshape(d, KV * hd), quant).reshape(L, KV, hd)
    v = mm(h, W["attn/wv"].reshape(d, KV * hd), quant).reshape(L, KV, hd)
    q, k = rope(q, s.rope_theta), rope(k, s.rope_theta)
    sc = jnp.einsum("qkgd,ckd->kgqc", q.reshape(L, KV, H // KV, hd), k,
                    precision=HI) / np.sqrt(hd)
    sc = jnp.where(jnp.tril(jnp.ones((L, L), bool)), sc, -jnp.inf)
    ctx = jnp.einsum("kgqc,ckd->qkgd", jax.nn.softmax(sc, -1), v,
                     precision=HI).reshape(L, H * hd)
    x = x + mm(ctx, W["attn/wo"].reshape(H * hd, d), quant)
    h = rms(x, W["ln2"], s.rms_eps)
    ff = jax.nn.silu(mm(h, W["mlp/wg"], quant)) * mm(h, W["mlp/wi"], quant)
    return x + mm(ff, W["mlp/wo"], quant)


def served_gaps(s: Spec, seed: int, samples, control: bool = False):
    key = seed_key(seed)
    specs = leaves(s)
    bf16 = jnp.bfloat16

    def gen(path, layer=0):
        return layer_leaf(key, path, specs[path], layer, bf16).astype(
            jnp.float32)

    layer_ref = jax.jit(partial(_layer, s, False))
    layer_ctl = jax.jit(partial(_layer, s, True))
    embed = gen("embed")
    xs, xc, meta = [], [], []
    for prompt, served in samples:
        ids, pos, toks, n = pad_sample(prompt, served)
        xs.append(embed[ids])
        xc.append(embed[ids] if control else None)
        meta.append((pos, toks, n))
    # the prefix layer, then the unit's layers 0 .. layers - 2
    for where, layer in [("prefix/0", 0)] + [("units/b0", u)
                                              for u in range(s.layers - 1)]:
        W = {n: gen(f"{where}/{n}", layer) for n in NAMES}
        xs = [layer_ref(x, W) for x in xs]
        if control:
            xc = [layer_ctl(x, W) for x in xc]
    gaps = jax.jit(partial(final, s.rms_eps))
    served, ctl = [], []
    for x, c, (pos, toks, n) in zip(xs, xc, meta):
        g, cg = gaps(x, c, gen("final_ln"), embed.T, pos, toks)
        served.append(np.asarray(g)[:n])
        if control:
            ctl.append(np.asarray(cg)[:n])
    return (np.concatenate(served),
            np.concatenate(ctl) if control else None)
