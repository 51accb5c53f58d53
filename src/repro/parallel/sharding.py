"""Sharding rules: logical axis names → mesh axes, plus param/optimizer/cache
PartitionSpec derivation for every architecture.

Logical activation axes: ("batch", "seq", "embed", "heads", "kv_heads",
"ffn", "experts", "vocab", "lru", "seq_kv").  Default mapping (single-pod
(data, model) mesh; multi-pod prepends "pod" onto the batch axis):

  batch   → ("pod","data")      ffn/heads/experts/vocab/lru → "model"
  embed   → None (replicated)   seq → None
  seq_kv  → "model"             (decode: flash-decode-style KV-sequence
                                 sharding — the SPMD partitioner turns the
                                 softmax max/sum and the PV einsum into the
                                 log-sum-exp merge all-reduces)

Step factories override rules per mode via `axis_rules(...)` (e.g. batch=1
long-context decode replicates batch and spreads seq_kv over data+model).

`constrain(x, *logical_axes)` inserts with_sharding_constraint when a mesh
context is active (jax.sharding.use_mesh / `with mesh:`), else no-op — model
code stays runnable on a single CPU device for smoke tests.

Parameter sharding is derived from leaf *path names* (wq/wk/wo/wi/...), with
optional FSDP: the first replicated dimension divisible by the data-axis size
is sharded over "data" (params+grads+optimizer state — ZeRO-3-style for the
working copy; the optimizer state reuses the same spec, which is what makes
it ZeRO and not mere TP).
"""

from __future__ import annotations

import contextlib
from typing import Any

import jax
from jax.sharding import PartitionSpec as P


# logical name → mesh axis (or tuple), for the canonical 2D/3D meshes
_DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_kv": "model",  # decode KV-sequence sharding (flash-decode analogue)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "experts": "model",
    "vocab": "model",
    "lru": "model",
    "conv": None,
    "unit": None,  # scan/stack axis — never sharded
}

_rules_stack: list[dict] = [dict(_DEFAULT_RULES)]


@contextlib.contextmanager
def axis_rules(**overrides):
    """Temporarily override logical-axis rules (step factories use this to
    retarget `batch`/`seq_kv` per mode/shape)."""
    top = dict(_rules_stack[-1])
    top.update(overrides)
    _rules_stack.append(top)
    try:
        yield
    finally:
        _rules_stack.pop()


def current_rules() -> dict:
    return _rules_stack[-1]


def mesh_axes() -> dict[str, int]:
    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.axis_names:
        return {}
    return {name: size for name, size in m.shape_tuple}


def _resolve(name: str, avail: dict[str, int], dim_size: int | None = None):
    """Map one logical name to a mesh-axis entry, dropping axes that are
    missing from the mesh or that do not divide `dim_size`."""
    rule = current_rules().get(name)
    if rule is None:
        return None
    if isinstance(rule, str):
        rule = (rule,)
    picked = []
    prod = 1
    for a in rule:
        if a not in avail:
            continue
        if dim_size is not None and dim_size % (prod * avail[a]):
            continue
        picked.append(a)
        prod *= avail[a]
    if not picked:
        return None
    return tuple(picked) if len(picked) > 1 else picked[0]


def spec_for(*logical, dim_sizes=None) -> P:
    """PartitionSpec for the current mesh (unknown logical names replicate;
    mesh axes not present are dropped; axes that don't divide the dim are
    dropped when dim_sizes is given). The same rules serve 1-device,
    single-pod and multi-pod meshes."""
    avail = mesh_axes()
    out = []
    for i, name in enumerate(logical):
        ds = dim_sizes[i] if dim_sizes is not None else None
        out.append(_resolve(name, avail, ds) if name else None)
    return P(*out)


def constrain_tree(tree, spec_tree):
    """with_sharding_constraint over matching pytrees (PartitionSpec is a
    pytree node, so plain tree_map would descend into it)."""
    flat, tdef = jax.tree_util.tree_flatten(tree)
    specs = tdef.flatten_up_to(spec_tree)
    return tdef.unflatten(
        [jax.lax.with_sharding_constraint(x, s) for x, s in zip(flat, specs)]
    )


def constrain(x, *logical):
    """with_sharding_constraint under an active mesh; identity otherwise."""
    if not mesh_axes():
        return x
    return jax.lax.with_sharding_constraint(x, spec_for(*logical, dim_sizes=x.shape[: len(logical)]))


# ------------------------------------------------------------------------
# parameter specs (path-name based)
# ------------------------------------------------------------------------

# leaf name → logical axes per dimension (excluding any leading stacked-unit
# axis, which is added automatically for leaves under "units").
_PARAM_AXES: dict[str, tuple[str | None, ...]] = {
    # embeddings / head
    "embed": ("vocab", "embed"),
    "head": ("embed", "vocab"),
    "final_ln": (None,),
    # attention
    "wq": ("embed", "heads", None),
    "wk": ("embed", "kv_heads", None),
    "wv": ("embed", "kv_heads", None),
    "wo_attn": ("heads", None, "embed"),
    "bq": ("heads", None),
    "bk": ("kv_heads", None),
    "bv": ("kv_heads", None),
    # dense mlp
    "wi": ("embed", "ffn"),
    "wg": ("embed", "ffn"),
    "wo_mlp": ("ffn", "embed"),
    # moe (leading experts dim)
    "router": ("embed", None),
    "wi_moe": ("experts", "embed", None),
    "wg_moe": ("experts", "embed", None),
    "wo_moe": ("experts", None, "embed"),
    # rg-lru temporal block
    "w_y": ("embed", "lru"),
    "w_x": ("embed", "lru"),
    "conv": (None, "lru"),
    "w_a": (None, "lru"),
    "w_i": (None, "lru"),
    "b_a": ("lru",),
    "b_i": ("lru",),
    "lam": ("lru",),
    "w_out": ("lru", "embed"),
    # xlstm (names from models/xlstm.py; d_in plays the "lru" role)
    "w_up": ("embed", "lru"),
    "w_gate": ("embed", "lru"),
    "w_down": ("lru", "embed"),
    "wq_rnn": ("lru", None, None),
    "wk_rnn": ("lru", None, None),
    "wv_rnn": ("lru", None, None),
    "w_if": ("lru", None, None),
    "ln": (None,),
}


def _leaf_logical(path) -> tuple[str, ...] | None:
    """Resolve the logical axes for a param leaf from its tree path."""
    names = [getattr(k, "key", getattr(k, "name", None)) or str(getattr(k, "idx", k)) for k in path]
    leaf = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    if leaf == "wo":
        if parent == "attn":
            key = "wo_attn"
        elif parent == "moe":
            key = "wo_moe"
        else:
            key = "wo_mlp"  # mlp / shared
    elif leaf in ("wi", "wg") and parent == "moe":
        key = leaf + "_moe"
    elif leaf in ("wq", "wk", "wv") and parent != "attn":
        key = leaf + "_rnn"  # mLSTM q/k/v live on the up-projected width
    elif leaf.startswith("ln") or leaf.endswith("ln"):
        key = "final_ln"
    else:
        key = leaf
    return _PARAM_AXES.get(key)


def param_pspecs(params, *, fsdp: bool = False, fsdp_axis: str = "data"):
    """PartitionSpecs for a parameter pytree. Leaves under 'units' get a
    leading replicated (stack) dim. With fsdp=True, the first replicated dim
    divisible by the fsdp axis is sharded over it."""
    avail = mesh_axes()

    def one(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        logical = _leaf_logical(path)
        stacked = "units" in names
        shape = leaf.shape[1:] if stacked else leaf.shape
        if logical is None:
            logical = (None,) * len(shape)
        entries = [
            _resolve(ax, avail, shape[i]) if ax else None
            for i, ax in enumerate(logical[: len(shape)])
        ]
        entries += [None] * (len(shape) - len(entries))
        # Never FSDP the embedding/head: with tied embeddings the head is the
        # transpose, so a data-sharded d_model axis would make the CE einsum
        # contract over `data` — the partitioner then materializes and
        # all-reduces FULL-batch logits (measured: 40 GB/chip on qwen2-0.5b).
        # Vocab sharding already divides these tables 16-way.
        if fsdp and fsdp_axis in avail and "vocab" not in logical:
            n = avail[fsdp_axis]
            for i, e in enumerate(entries):
                if e is None and shape[i] % n == 0 and shape[i] >= 2 * n:
                    entries[i] = fsdp_axis
                    break
        if stacked:
            entries = [None] + entries
        return P(*entries)

    return jax.tree_util.tree_map_with_path(one, params)


def opt_pspecs(param_specs, opt_state_proto):
    """Optimizer-state specs: step replicated; mu/nu/master shaped like params
    (ZeRO — they inherit the param specs, including the fsdp axis)."""
    from ..optim.adamw import OptState

    return OptState(
        step=P(),
        mu=param_specs,
        nu=param_specs,
        master=param_specs,
    )


# ------------------------------------------------------------------------
# cache / state specs
# ------------------------------------------------------------------------


def cache_pspecs(caches: Any):
    """Specs for decode caches/states by leaf name:
    k/v (B,C,KV,hd) → (batch, seq_kv, kv_heads?, None); pos (B,C);
    ptr (B,); recurrent h (B,W) → (batch, lru); conv tails (B,k,W);
    mlstm C/n/m per shape. Leaves under 'units' carry a leading stack dim."""
    avail = mesh_axes()

    def one(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", None)) for k in path]
        stacked = "units" in names
        shape = leaf.shape[1:] if stacked else leaf.shape
        leafname = names[-1]
        if leafname in ("k", "v"):
            entries = [
                _resolve("batch", avail, shape[0]),
                _resolve("seq_kv", avail, shape[1]),
                _resolve("kv_heads", avail, shape[2]),
                None,
            ]
            # never double-assign: if seq took 'model', kv_heads rule would
            # conflict — seq_kv and kv_heads share 'model'; prefer seq_kv.
            if entries[1] is not None:
                entries[2] = None
        elif leafname == "pos":
            entries = [_resolve("batch", avail, shape[0]), _resolve("seq_kv", avail, shape[1])]
        elif leafname == "ptr":
            entries = []  # scalar cursor — replicated
        elif leafname == "conv":
            entries = [_resolve("batch", avail, shape[0]), None, _resolve("lru", avail, shape[2])]
        elif leafname == "h" and len(shape) == 2:
            entries = [_resolve("batch", avail, shape[0]), _resolve("lru", avail, shape[1])]
        else:
            # mlstm C/n/m, slstm c/n/h/m: batch-sharded, rest replicated
            entries = [_resolve("batch", avail, shape[0])] + [None] * (len(shape) - 1)
        if stacked:
            entries = [None] + entries
        return P(*entries)

    return jax.tree_util.tree_map_with_path(one, caches)


def batch_pspecs(batch: Any):
    """Input-batch specs: leading dim is batch; everything else replicated,
    except trailing embedding dims of frontend stubs."""

    def one(path, leaf):
        entries = [_resolve("batch", mesh_axes(), leaf.shape[0])] + [None] * (leaf.ndim - 1)
        return P(*entries)

    return jax.tree_util.tree_map_with_path(one, batch)
