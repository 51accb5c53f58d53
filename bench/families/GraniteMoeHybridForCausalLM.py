"""The Granite 4.0-H family (``"architectures":
["GraniteMoeHybridForCausalLM"]``, HF ``model_type`` granitemoehybrid)
without experts: a decoder whose layers are Mamba-2 mixers or grouped-query
attention without a position embedding, each followed by a SwiGLU MLP,
with Granite's scalars (embeddings × ``embedding_multiplier``, each
block's output × ``residual_multiplier``, softmax scale
``attention_multiplier``, logits ÷ ``logits_scaling``).

A family module gives the harness what it needs of one architecture
(`bench/benchkit/spec.py`; the Qwen2 family's docstring lists the five
functions).  The layers are laid out as the program's repeated unit: the
smallest period of ``layer_types`` (10 for granite-4.0-h-micro: Mamba-2
×5, attention, Mamba-2 ×4) stacked over its repeats, ``units/b<j>``.

The reference (`served_gaps`), in float32 at ``HIGHEST``, over whole
sequences, drawn layer by layer from the seed, writes the published
layer out:

    x = embed[ids] · embedding_multiplier
    each layer:  x += r · mixer(RMSNorm(x));  x += r · MLP(RMSNorm(x))
    Mamba-2:     [z | xBC | dt] = in_proj(x);  xBC = SiLU(conv1d(xBC) + b)
                 dt = softplus(dt + dt_bias);  A = −exp(A_log)
                 h_t = exp(dt_t·A)·h_{t−1} + dt_t·x_t B_tᵀ,  y_t = h_t C_t + D·x_t
                 out_proj(RMSNorm(y · SiLU(z)) · norm)   (one group)
    attention:   softmax(q kᵀ · attention_multiplier) v, no RoPE, no bias
    logits = RMSNorm(x) · embedᵀ / logits_scaling

Departures from the published model: none in the arithmetic.  The
reference runs the Mamba-2 recurrence token by token (the program scans
chunks of ``mamba_chunk_size``); its int8 control quantizes the linear
layers (in_proj, out_proj, q/k/v/o, the MLP and the head), not the
depthwise conv or the state.  The MLP's gate and up halves
(``shared_mlp.input_linear``) are two matrices, ``mlp/wg`` and
``mlp/wi``.  The weights are random (`model.py`).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchkit.model import BF16_BYTES, Leaf, layer_leaf, seed_key
from benchkit.reference import HI, final, mm, pad_sample, rms

F32_BYTES = 4
KINDS = {"mamba": "mamba2", "attention": "attn"}  # layer_types → block kind


@dataclass(frozen=True)
class GraniteSpec:
    """A Granite 4.0-H decoder, sized by its published `config.json`."""

    name: str
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    layer_types: tuple  # "mamba" | "attention", per layer
    m_heads: int
    m_head_dim: int
    m_state: int
    m_conv: int
    m_chunk: int
    rms_eps: float
    embed_mult: float
    residual_mult: float
    attn_scale: float
    logits_div: float

    @classmethod
    def from_config(cls, cfg: dict) -> "GraniteSpec":
        h, n = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
        return cls(
            name=cfg["name"], hidden=h, heads=n,
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim") or h // n),
            ffn=int(cfg["shared_intermediate_size"]),
            vocab=int(cfg["vocab_size"]),
            layer_types=tuple(cfg["layer_types"]),
            m_heads=int(cfg["mamba_n_heads"]), m_head_dim=int(cfg["mamba_d_head"]),
            m_state=int(cfg["mamba_d_state"]), m_conv=int(cfg["mamba_d_conv"]),
            m_chunk=int(cfg["mamba_chunk_size"]),
            rms_eps=float(cfg["rms_norm_eps"]),
            embed_mult=float(cfg["embedding_multiplier"]),
            residual_mult=float(cfg["residual_multiplier"]),
            attn_scale=float(cfg["attention_multiplier"]),
            logits_div=float(cfg["logits_scaling"]))

    # ------------------------------------------------------------ sizes --

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def period(self) -> int:
        """The smallest period of ``layer_types``: the program's unit."""
        L = self.layers
        return next(p for p in range(1, L + 1) if L % p == 0 and all(
            self.layer_types[i] == self.layer_types[i % p] for i in range(L)))

    @property
    def inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.m_state  # x, B, C (one group)

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    def mamba_matmul_params(self) -> int:
        d, di = self.hidden, self.inner
        return d * (di + self.conv_dim + self.m_heads) + di * d + 3 * d * self.ffn

    def attn_matmul_params(self) -> int:
        d, hd = self.hidden, self.head_dim
        return (d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d
                + 3 * d * self.ffn)

    def params(self) -> int:
        mamba = (self.mamba_matmul_params() + (self.m_conv + 1) * self.conv_dim
                 + 3 * self.m_heads + self.inner + 2 * self.hidden)
        attn = self.attn_matmul_params() + 2 * self.hidden
        return (self.count("mamba") * mamba + self.count("attention") * attn
                + self.vocab * self.hidden + self.hidden)  # tied head

    def state_bytes_per_row(self) -> int:
        """One row's Mamba-2 state in every Mamba-2 layer, f32: the SSM
        state and the conv tail."""
        per = (self.m_heads * self.m_head_dim * self.m_state
               + (self.m_conv - 1) * self.conv_dim)
        return self.count("mamba") * per * F32_BYTES

    def kv_bytes_per_token(self) -> int:
        """K and V of one token in every attention layer, bf16."""
        return (self.count("attention") * 2 * self.kv_heads * self.head_dim
                * BF16_BYTES)


def spec(cfg: dict) -> GraniteSpec:
    return GraniteSpec.from_config(cfg)


def call_cost(spec: GraniteSpec, call) -> tuple[float, float]:
    """FLOPs and HBM bytes that one decode call needs, whatever implements
    it.  ``call.contexts`` holds, for each row that takes part, the number
    of earlier tokens of its sequence (its position).

    Bytes: every weight read once (the tied embedding once, as the head),
    each live row's Mamba-2 state (SSM state and conv tail, f32) read once
    and written once, its earlier K/V entries in the attention layers read
    once and its new entries written, and its f32 logits written.  Rows
    that take no part need nothing.  FLOPs, per live row: 2 per
    multiply-add of every matmul (layers and head); QK^T and PV over the
    context including the new token in each attention layer; in each
    Mamba-2 layer the state update (3 per state element: the decay, the
    input product and the add) and the readout h·C (2 per element).  The
    depthwise conv, norms and gates are left out."""
    ctx = np.asarray(call.contexts, np.int64)
    rows = len(ctx)
    if rows == 0:
        return 0.0, 0.0
    head = spec.vocab * spec.hidden
    nbytes = (spec.params() * BF16_BYTES + rows * 2 * spec.state_bytes_per_row()
              + spec.kv_bytes_per_token() * (int(ctx.sum()) + rows)
              + rows * spec.vocab * F32_BYTES)
    per_row = 2 * (spec.count("mamba") * spec.mamba_matmul_params()
                   + spec.count("attention") * spec.attn_matmul_params() + head)
    per_row += 5 * spec.count("mamba") * spec.m_heads * spec.m_head_dim * spec.m_state
    attn = 4 * spec.count("attention") * spec.heads * spec.head_dim * (ctx + 1)
    return float(rows * per_row + int(attn.sum())), float(nbytes)


def program_config(cfg: dict):
    """The program's `ModelConfig`: the registry entry named by
    ``program_arch`` with every published size and scalar taken from the
    file, the unit from the period of ``layer_types``.  Raises, naming the
    key, where a published value is one the program cannot state."""
    from repro.configs.base import Block
    from repro.configs.registry import ARCH_IDS, get_config

    if cfg["program_arch"] not in ARCH_IDS:
        raise ValueError(f"the program has no {cfg['program_arch']!r} "
                         f"(repro.configs.registry)")
    s = spec(cfg)
    stated = {"hidden_act": (cfg.get("hidden_act"), "silu"),
              "attention_bias": (bool(cfg.get("attention_bias")), False),
              "position_embedding_type": (cfg.get("position_embedding_type"), "nope"),
              "num_local_experts": (int(cfg.get("num_local_experts", 0)), 0),
              "mamba_n_groups": (int(cfg["mamba_n_groups"]), 1),
              "mamba_conv_bias": (bool(cfg["mamba_conv_bias"]), True),
              "mamba_proj_bias": (bool(cfg["mamba_proj_bias"]), False),
              "mamba_expand": (int(cfg["mamba_expand"]) * s.hidden, s.inner),
              "normalization_function": (cfg.get("normalization_function"), "rmsnorm"),
              "tie_word_embeddings": (bool(cfg["tie_word_embeddings"]), True),
              "layer_types": (set(s.layer_types) <= set(KINDS), True)}
    for key, (published, program) in stated.items():
        if published != program:
            raise ValueError(f"{cfg['name']}: published {key} = {published!r}, but "
                             f"the program can only state {program!r}")
    p = s.period
    return dataclasses.replace(
        get_config(cfg["program_arch"]), d_model=s.hidden, n_heads=s.heads,
        n_kv_heads=s.kv_heads, head_dim=s.head_dim, d_ff=s.ffn, vocab=s.vocab,
        unit=tuple(Block(KINDS[t]) for t in s.layer_types[:p]),
        num_units=s.layers // p, prefix=(), suffix=(), tie_embeddings=True,
        rms_eps=s.rms_eps, rope=False, attn_scale=s.attn_scale,
        embed_mult=s.embed_mult, residual_mult=s.residual_mult,
        logits_div=s.logits_div, mamba_heads=s.m_heads,
        mamba_head_dim=s.m_head_dim, mamba_state=s.m_state, mamba_conv=s.m_conv,
        mamba_chunk=s.m_chunk)


def _layer_leaves(s: GraniteSpec, kind: str) -> dict[str, Leaf]:
    d, F = s.hidden, s.ffn
    out = {"ln1": Leaf((d,), "norm"), "ln2": Leaf((d,), "norm"),
           "mlp/wi": Leaf((d, F), "matrix", d), "mlp/wg": Leaf((d, F), "matrix", d),
           "mlp/wo": Leaf((F, d), "matrix", F)}
    if kind == "attention":
        H, KV, hd = s.heads, s.kv_heads, s.head_dim
        out.update({"attn/wq": Leaf((d, H, hd), "matrix", d),
                    "attn/wk": Leaf((d, KV, hd), "matrix", d),
                    "attn/wv": Leaf((d, KV, hd), "matrix", d),
                    "attn/wo": Leaf((H, hd, d), "matrix", H * hd)})
    else:
        H, di, cd, K = s.m_heads, s.inner, s.conv_dim, s.m_conv
        out.update({"mixer/in_proj": Leaf((d, di + cd + H), "matrix", d),
                    "mixer/conv_w": Leaf((K, cd), "matrix", K),
                    "mixer/conv_b": Leaf((cd,), "bias"),
                    "mixer/dt_bias": Leaf((H,), "bias"),
                    "mixer/A_log": Leaf((H,), "bias"),
                    "mixer/D": Leaf((H,), "norm"),
                    "mixer/norm": Leaf((di,), "norm"),
                    "mixer/out_proj": Leaf((di, d), "matrix", di)})
    return out


def leaves(s: GraniteSpec) -> dict[str, Leaf]:
    """Matrices N(0, 1/fan_in) (the conv of fan-in K); norms and D
    1 + 0.1 N; A_log, dt_bias and the conv bias 0.1 N.  The embedding is
    a matrix of fan-in d · embedding_multiplier², so that the multiplied
    embedding has the N(0, 1/d) scale of a plain one: drawn N(0, 1/d), the
    ×12 embedding would outweigh the 80 blocks' outputs, the tied head
    would put the last token first at every step, and greedy decoding
    would copy it, so that no gap, the program's or the int8 control's,
    could show.  Layer j of each period under ``units/b<j>``, stacked over
    the periods."""
    out = {"embed": Leaf((s.vocab, s.hidden), "matrix",
                         round(s.hidden * s.embed_mult**2)),
           "final_ln": Leaf((s.hidden,), "norm")}
    units = s.layers // s.period
    for j, kind in enumerate(s.layer_types[:s.period]):
        out.update({f"units/b{j}/{n}": dataclasses.replace(v, stacked=units)
                    for n, v in _layer_leaves(s, kind).items()})
    return out


# ----------------------------------------------------------- reference ----


def _mlp(s: GraniteSpec, quant: bool, x, W):
    h = rms(x, W["ln2"], s.rms_eps)
    ff = jax.nn.silu(mm(h, W["mlp/wg"], quant)) * mm(h, W["mlp/wi"], quant)
    return x + s.residual_mult * mm(ff, W["mlp/wo"], quant)


def _attention_layer(s: GraniteSpec, quant: bool, x, W):
    """An attention layer over a whole sequence ``x`` (L, d)."""
    L, d = x.shape
    H, KV, hd = s.heads, s.kv_heads, s.head_dim
    h = rms(x, W["ln1"], s.rms_eps)
    q = mm(h, W["attn/wq"].reshape(d, H * hd), quant).reshape(L, KV, H // KV, hd)
    k = mm(h, W["attn/wk"].reshape(d, KV * hd), quant).reshape(L, KV, hd)
    v = mm(h, W["attn/wv"].reshape(d, KV * hd), quant).reshape(L, KV, hd)
    sc = jnp.einsum("qkgd,ckd->kgqc", q, k, precision=HI) * s.attn_scale
    sc = jnp.where(jnp.tril(jnp.ones((L, L), bool)), sc, -jnp.inf)
    ctx = jnp.einsum("kgqc,ckd->qkgd", jax.nn.softmax(sc, -1), v,
                     precision=HI).reshape(L, H * hd)
    x = x + s.residual_mult * mm(ctx, W["attn/wo"].reshape(H * hd, d), quant)
    return _mlp(s, quant, x, W)


def _mamba_layer(s: GraniteSpec, quant: bool, x, W):
    """A Mamba-2 layer over a whole sequence ``x`` (L, d), the recurrence
    one token at a time from a zero state."""
    L = x.shape[0]
    H, P, N, di, K = s.m_heads, s.m_head_dim, s.m_state, s.inner, s.m_conv
    h = rms(x, W["ln1"], s.rms_eps)
    zxbcdt = mm(h, W["mixer/in_proj"], quant)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + s.conv_dim],
                  zxbcdt[:, di + s.conv_dim:])
    xp = jnp.concatenate([jnp.zeros((K - 1, s.conv_dim)), xbc])
    w = W["mixer/conv_w"]
    xbc = jax.nn.silu(sum(xp[i:i + L] * w[i] for i in range(K)) + W["mixer/conv_b"])
    xs = xbc[:, :di].reshape(L, H, P)
    B, C = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + W["mixer/dt_bias"])
    A = -jnp.exp(W["mixer/A_log"])

    def step(st, inp):
        xt, dtt, bt, ct = inp
        st = jnp.exp(dtt * A)[:, None, None] * st + (dtt[:, None] * xt)[..., None] * bt
        return st, jnp.einsum("hpn,n->hp", st, ct, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (xs, dt, B, C))
    y = (y + W["mixer/D"][:, None] * xs).reshape(L, di) * jax.nn.silu(z)
    y = rms(y, W["mixer/norm"], s.rms_eps)
    x = x + s.residual_mult * mm(y, W["mixer/out_proj"], quant)
    return _mlp(s, quant, x, W)


def reference_hidden(s: GraniteSpec, seed: int, samples, control: bool = False):
    """Each sample's padded sequence (`reference.pad_sample`) through every
    layer: the hidden states before the final norm (and the control's),
    the final norm's scale, the head (÷ ``logits_scaling``) and each
    sample's (positions, tokens, served count)."""
    key = seed_key(seed)
    specs = leaves(s)
    bf16 = jnp.bfloat16

    @partial(jax.jit, static_argnums=1)
    def gen_layer(key, j, u):
        names = _layer_leaves(s, s.layer_types[j])
        return {n: layer_leaf(key, f"units/b{j}/{n}", specs[f"units/b{j}/{n}"], u,
                              bf16).astype(jnp.float32) for n in names}

    gen = jax.jit(lambda key, path: layer_leaf(
        key, path, specs[path], 0, bf16).astype(jnp.float32), static_argnums=1)
    fns = {kind: (jax.jit(partial(f, s, False)), jax.jit(partial(f, s, True)))
           for kind, f in (("mamba", _mamba_layer), ("attention", _attention_layer))}

    embed = gen(key, "embed")
    xs, xc, meta = [], [], []
    for prompt, served in samples:
        ids, pos, toks, n = pad_sample(prompt, served)
        xs.append(embed[ids] * s.embed_mult)
        xc.append(xs[-1] if control else None)
        meta.append((pos, toks, n))
    head = embed.T / s.logits_div
    del embed
    for layer, kind in enumerate(s.layer_types):
        W = gen_layer(key, layer % s.period, jnp.int32(layer // s.period))
        ref, ctl = fns[kind]
        xs = [ref(x, W) for x in xs]
        if control:
            xc = [ctl(x, W) for x in xc]
    return xs, xc, gen(key, "final_ln"), head, meta


def served_gaps(s: GraniteSpec, seed: int, samples, control: bool = False):
    """``samples``: list of (prompt tokens, served tokens).  Returns the
    per-token gaps of the served tokens, concatenated over samples, and
    those of the int8 control's tokens (None without ``control``)."""
    xs, xc, norm, head, meta = reference_hidden(s, seed, samples, control)
    gaps = jax.jit(partial(final, s.rms_eps))
    served, ctl = [], []
    for x, c, (pos, toks, n) in zip(xs, xc, meta):
        g, cg = gaps(x, c, norm, head, pos, toks)
        served.append(np.asarray(g)[:n])
        if control:
            ctl.append(np.asarray(cg)[:n])
    return (np.concatenate(served),
            np.concatenate(ctl) if control else None)
