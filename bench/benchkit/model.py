"""The served model as the benchmark sees it: its sizes from the cell's
configuration file, the operations and bytes a decode call needs, and
the seeded weights.

Everything here is the yardstick's own.  The program is told its sizes
through `repro.configs.base.ModelConfig` (built in `program_config`), and
its weights are made here, so the plain reference can make the same
values again without taking anything from the program.
"""

from __future__ import annotations

import dataclasses
import zlib
from dataclasses import dataclass

import numpy as np

BF16_BYTES = 2


@dataclass(frozen=True)
class ModelSpec:
    """A Qwen2-style decoder (RMSNorm, RoPE, GQA attention with q/k/v
    bias, SwiGLU MLP), sized by its published `config.json` keys."""

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


def decode_call_cost(spec: ModelSpec, contexts) -> tuple[float, float]:
    """FLOPs and HBM bytes that one decode call needs, whatever implements
    it.  ``contexts`` holds, for each row that writes a live entry, the
    number of earlier tokens of its sequence (its position).

    Bytes: every weight read once (the LM head once; an untied embedding
    only as the rows gathered), each live row's earlier K/V entries read
    once, its new entry written once, and its f32 logits written.  Rows
    that take no part in the call need nothing.  FLOPs: 2 per multiply-add
    of every matmul (layers and head) for each live row, plus QK^T and PV
    over its context including the new token."""
    ctx = np.asarray(contexts, np.int64)
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
    architecture the file describes."""
    from repro.configs.registry import get_config

    base = get_config(cfg["program_arch"])
    spec = ModelSpec.from_config(cfg)
    plain = (len(base.unit) == 1 and base.unit[0].kind == "attn"
             and base.unit[0].window == 0 and not base.prefix
             and not base.suffix and base.mlp_kind == "swiglu"
             and base.qkv_bias and not base.norm_plus_one
             and not base.embed_scale and not base.sandwich_norms)
    if not plain:
        raise ValueError(f"{cfg['program_arch']} is not a Qwen2-style stack")
    return dataclasses.replace(
        base, d_model=spec.hidden, n_heads=spec.heads,
        n_kv_heads=spec.kv_heads, head_dim=spec.head_dim, d_ff=spec.ffn,
        vocab=spec.vocab, num_units=spec.layers, rope_theta=spec.rope_theta,
        tie_embeddings=spec.tied)


# ------------------------------------------------------------- weights ----
#
# Every leaf is drawn from its own key: fold_in(fold_in(seed key, crc32 of
# its path), layer).  Matrices are N(0, 1/fan_in); RMSNorm scales 1 + 0.1 N;
# q/k/v biases 0.1 N.  The leaves are laid out as the program's
# `models.transformer.init_params` lays them (checked by `check_tree`).
# The reference draws each layer again with `layer_leaf`; XLA may round a
# rare element of the f32 draw differently inside the larger program, so
# the two agree to the last bit of bf16 on all but about one in 10^5.


@dataclass(frozen=True)
class Leaf:
    shape: tuple  # of one layer for stacked leaves
    kind: str  # "matrix" | "norm" | "bias"
    fan_in: int = 1
    stacked: bool = False


def leaves(spec: ModelSpec) -> dict[str, Leaf]:
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
    out.update({k: dataclasses.replace(v, stacked=True)
                for k, v in layer.items()})
    return out


def seed_key(seed: int):
    """A threefry key from all 64 bits of ``seed`` (JAX's own seeding
    keeps only 32 without x64)."""
    import jax.numpy as jnp

    words = np.random.SeedSequence([int(seed) & (2**64 - 1), 0x5EED])
    return jnp.asarray(words.generate_state(2, np.uint32), jnp.uint32)


def _draw(key, leaf: Leaf, dtype):
    import jax
    import jax.numpy as jnp

    z = jax.random.normal(key, leaf.shape, jnp.float32)
    if leaf.kind == "matrix":
        z = z * (leaf.fan_in ** -0.5)
    elif leaf.kind == "norm":
        z = 1.0 + 0.1 * z
    else:
        z = 0.1 * z
    return z.astype(dtype)


def leaf_key(key, path: str, layer: int = 0):
    import jax

    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(k, layer)


def layer_leaf(key, path: str, leaf: Leaf, layer: int, dtype):
    """One layer's slice of a leaf, as `make_params` draws it."""
    return _draw(leaf_key(key, path, layer), leaf, dtype)


def make_params(spec: ModelSpec, seed: int, dtype):
    """Every weight in ``dtype``, on the device, in one jitted call."""
    import jax

    specs = leaves(spec)

    def build(key):
        tree: dict = {}
        for path, leaf in specs.items():
            if leaf.stacked:
                keys = jax.vmap(lambda l, p=path: leaf_key(key, p, l))(
                    jax.numpy.arange(spec.layers))
                val = jax.vmap(lambda k, lf=leaf: _draw(k, lf, dtype))(keys)
            else:
                val = _draw(leaf_key(key, path), leaf, dtype)
            node = tree
            *parents, last = path.split("/")
            for p in parents:
                node = node.setdefault(p, {})
            node[last] = val
        return tree

    return jax.jit(build)(seed_key(seed))


def tree_paths(tree) -> dict[str, tuple]:
    """path → (shape, dtype name) of every leaf of a params pytree."""
    import jax

    out = {}
    for kp, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        out[path] = (tuple(x.shape), str(x.dtype))
    return out


def check_tree(a: dict, b: dict) -> None:
    """Raise unless two `tree_paths` listings (the benchmark's weights and
    the program's) have the same leaves, shapes and dtypes."""
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))
        raise ValueError(f"the program's parameter tree differs from the "
                         f"benchmark's: {diff[:6]}")
