"""The decode call's in-place ring write against the one-hot write.

Where the ring's sequence axis is not sharded, a decode block attends over
the ring as it stood beside its new entry, and `decode_step` writes the
entries into their slot after the layer scan (`cache.kv_cache_write_slot`).
Under an active ``seq_kv`` rule the block keeps `kv_cache_append`'s
one-hot write.  Both must give the same logits and leave the same ring:
through a ring that wraps twice, rows at position -1, rows reset part-way,
a windowed block and a prefix block."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import Block
from repro.configs.registry import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models.cache import seq_shard_axes
from repro.models.transformer import (
    decode_step,
    init_caches,
    init_params,
    reset_cache_row,
)

RING, CALLS, ROWS = 8, 20, 4
CFG = dataclasses.replace(
    get_smoke_config("qwen2-0.5b"), prefix=(Block("attn"),),
    unit=(Block("attn"), Block("attn", window=4)), num_units=2)
RESETS = {7: 1, 13: 2}  # call → row reset before it


def _positions():
    """(CALLS, ROWS, 1): row 0 live throughout, row 1 joins at call 3 and
    restarts at its reset, row 2 live every other call and restarts at its
    reset, row 3 never live."""
    pos = np.full((CALLS, ROWS, 1), -1, np.int32)
    nxt = [0] * ROWS
    for t in range(CALLS):
        for r in range(ROWS):
            if RESETS.get(t) == r:
                nxt[r] = 0
            if r == 0 or (r == 1 and t >= 3) or (r == 2 and t % 2 == 0):
                pos[t, r, 0] = nxt[r]
                nxt[r] += 1
    return pos


def _ring_leaves(caches):
    return {jax.tree_util.keystr(p): np.asarray(x)
            for p, x in jax.tree_util.tree_leaves_with_path(caches)}


@pytest.mark.parametrize("window_only", [False, True],
                         ids=["full_and_windowed", "windowed_unit"])
def test_in_place_write_matches_the_one_hot_write(window_only):
    cfg = CFG if not window_only else dataclasses.replace(
        CFG, prefix=(), unit=(Block("attn", window=4),))
    params = init_params(jax.random.PRNGKey(1), cfg, jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (CALLS, ROWS, 1), 1, cfg.vocab)
    pos = _positions()
    step = jax.jit(lambda p, t, q, c: decode_step(p, cfg, t, q, c))
    reset = jax.jit(lambda c, row: reset_cache_row(cfg, c, row))
    mesh = make_mesh((1,), ("model",))  # the seq_kv rule's axis: one-hot
    with jax.set_mesh(mesh):
        assert seq_shard_axes() == ["model"]
        ref_step = jax.jit(lambda p, t, q, c: decode_step(p, cfg, t, q, c))
    assert seq_shard_axes() == []
    first = "['prefix'][0]" if cfg.prefix else "['units']['b0']"

    fast = init_caches(cfg, ROWS, RING, jnp.float32)
    slow = init_caches(cfg, ROWS, RING, jnp.float32)
    for t in range(CALLS):
        if t in RESETS:
            fast, slow = reset(fast, RESETS[t]), reset(slow, RESETS[t])
        lf, fast = step(params, tokens[t], jnp.asarray(pos[t]), fast)
        with jax.set_mesh(mesh):
            ls, slow = ref_step(params, tokens[t], jnp.asarray(pos[t]), slow)
        np.testing.assert_allclose(np.asarray(lf), np.asarray(ls),
                                   rtol=1e-5, atol=1e-5, err_msg=f"call {t}")
        a, b = _ring_leaves(fast), _ring_leaves(slow)
        assert a.keys() == b.keys()
        for name in a:
            if name.endswith(("['pos']", "['ptr']")):
                np.testing.assert_array_equal(a[name], b[name], f"call {t} {name}")
                continue
            # k, v: a later layer's input holds an attention output whose
            # float sums run in another order; the first layer's entries
            # come from the embeddings alone, so they are the same bits
            np.testing.assert_allclose(a[name], b[name], rtol=1e-5, atol=1e-5,
                                       err_msg=f"call {t} {name}")
            if name.startswith(first):
                lead = (lambda x: x[0]) if "units" in name else (lambda x: x)
                np.testing.assert_array_equal(lead(a[name]), lead(b[name]),
                                              f"call {t} {name}")
    # every ring wrapped (the windowed one of 4 entries five times)
    assert all(int(np.ravel(x)[0]) == CALLS
               for n, x in _ring_leaves(fast).items() if n.endswith("['ptr']"))
