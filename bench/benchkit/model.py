"""The seeded weights of a served model, whatever its architecture.

A family module (`bench/families/<architecture>.py`) lists its leaves as
path → `Leaf`; everything here draws them.  Every leaf is drawn from its
own key: fold_in(fold_in(seed key, crc32 of its path), layer).  Matrices
are N(0, 1/fan_in); RMSNorm scales 1 + 0.1 N; biases 0.1 N.  A family
lays its leaves out as the program's `models.transformer.init_params`
does (checked by `check_tree`): a leaf stacked over the repeated unit's
layers holds them on its leading axis, and a path part that is a number
(``prefix/0/...``) is an index into a list.  The reference draws each
layer again with `layer_leaf`; XLA may round a rare element of the f32
draw differently inside the larger program, so the two agree to the last
bit of bf16 on all but about one in 10^5.

Everything here is the yardstick's own: the program is told its sizes by
the family's `program_config`, and its weights are made here, so the
plain reference can make the same values again without taking anything
from the program.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass

import numpy as np

BF16_BYTES = 2


@dataclass(frozen=True)
class Leaf:
    shape: tuple  # of one layer for stacked leaves
    kind: str  # "matrix" | "norm" | "bias"
    fan_in: int = 1
    stacked: int = 0  # layers stacked on the leading axis; 0: one array


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


def _lists(node):
    """Nested dicts whose keys are 0..n-1 (as strings) become lists."""
    if not isinstance(node, dict):
        return node
    out = {k: _lists(v) for k, v in node.items()}
    if out and sorted(out) == sorted(map(str, range(len(out)))):
        return [out[str(i)] for i in range(len(out))]
    return out


def make_params(specs: dict[str, Leaf], seed: int, dtype):
    """Every leaf of ``specs`` (a family's `leaves`) in ``dtype``, on the
    device, in one jitted call."""
    import jax

    def build(key):
        tree: dict = {}
        for path, leaf in specs.items():
            if leaf.stacked:
                keys = jax.vmap(lambda l, p=path: leaf_key(key, p, l))(
                    jax.numpy.arange(leaf.stacked))
                val = jax.vmap(lambda k, lf=leaf: _draw(k, lf, dtype))(keys)
            else:
                val = _draw(leaf_key(key, path), leaf, dtype)
            node = tree
            *parents, last = path.split("/")
            for p in parents:
                node = node.setdefault(p, {})
            node[last] = val
        return _lists(tree)

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
