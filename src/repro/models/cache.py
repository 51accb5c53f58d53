"""Decode-time state: KV caches (full + rolling sliding-window buffers) and
recurrent states (mLSTM / sLSTM / RG-LRU / Mamba-2).

All caches are functional pytrees. KV caches carry explicit per-slot
positions (``pos``, -1 = empty) so rolling buffers and continuous batching
(per-row lengths) need no implicit arithmetic, and attention masking is
uniform (see layers.attention_forward).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_kv_cache(batch: int, capacity: int, n_kv: int, head_dim: int, dtype):
    """ptr is a *scalar* write cursor: the serving engine is slot-synchronous
    (every active row writes its token into the same ring slot each step;
    per-row raggedness lives entirely in `pos`).  A scalar index keeps the
    decode-time cache update a dynamic-update-slice that the SPMD
    partitioner handles as a masked local write on the owning shard — a
    per-row scatter would force a full all-gather/rematerialization of the
    sequence-sharded cache (measured: 34 GB/chip on qwen2-72b decode_32k)."""
    return {
        "k": jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
        "pos": jnp.full((batch, capacity), -1, jnp.int32),
        "ptr": jnp.zeros((), jnp.int32),  # total tokens ever written (scalar)
    }


def kv_cache_write_prefill(cache, k, v, positions):
    """Bulk write a prefix: k/v (B,S,KV,hd), positions (B,S). S ≤ capacity.
    For rolling buffers with S > capacity the last `capacity` tokens land
    (standard sliding-window prefill).  All paths are static slices/pads —
    never a partial dynamic update of the (sequence-sharded) cache, which
    the SPMD partitioner would handle by replicating the cache."""
    B, S = positions.shape
    C = cache["k"].shape[1]
    if S == C:
        kc, vc, pc = k, v, positions
    elif S < C:
        pad = ((0, 0), (0, C - S), (0, 0), (0, 0))
        kc = jnp.pad(k, pad)
        vc = jnp.pad(v, pad)
        pc = jnp.pad(positions, ((0, 0), (0, C - S)), constant_values=-1)
    else:
        kc = jax.lax.slice_in_dim(k, S - C, S, axis=1)
        vc = jax.lax.slice_in_dim(v, S - C, S, axis=1)
        pc = jax.lax.slice_in_dim(positions, S - C, S, axis=1)
    return {"k": kc, "v": vc, "pos": pc, "ptr": cache["ptr"] + S}


def seq_shard_axes() -> list[str]:
    """The mesh axes that the active ``seq_kv`` rule spreads a KV ring's
    sequence axis over (empty without a mesh or such a rule)."""
    from ..parallel.sharding import current_rules, mesh_axes

    axes = mesh_axes()
    rule = current_rules().get("seq_kv")
    rule = (rule,) if isinstance(rule, str) else (rule or ())
    return [a for a in rule if a in axes]


def kv_cache_write_slot(cache, new, ax: int = 0):
    """Write one decode call's entries into a ring that is not sequence
    sharded: ``new`` holds k/v (…, B, 1, KV, hd), already in the cache's
    dtype, and pos (…, B, 1), with the batch at axis ``ax`` (1 for a
    unit-stacked cache).  One dynamic-update-slice per leaf at slot
    ptr mod capacity, in place when the cache is donated; the cursor
    advances by one.  The same ring as `kv_cache_append` leaves."""
    C = cache["pos"].shape[ax + 1]
    slot = jnp.ravel(cache["ptr"])[0] % C  # every layer's cursor is the same
    z = jnp.zeros((), jnp.int32)
    out = {"ptr": cache["ptr"] + 1}
    for name in ("k", "v", "pos"):
        at = (z,) * (ax + 1) + (slot,) + (z,) * (cache[name].ndim - ax - 2)
        out[name] = jax.lax.dynamic_update_slice(cache[name], new[name], at)
    return out


def kv_cache_append(cache, k, v, positions, ring_write: bool = False):
    """Append one token per row (decode). k/v (B,1,KV,hd), positions (B,1).
    Rolling ring buffer: every row writes slot = ptr mod capacity (scalar —
    see make_kv_cache); per-row positions go into `pos` at that slot.

    Two write paths (a plain dynamic-update-slice on the sequence-sharded
    dim is NOT one of them — GSPMD "involuntarily rematerializes" the whole
    cache for it, 34 GB/chip measured):

      ring_write=True (§Perf iteration B2): shard_map manual over the
        sequence-sharding axes — each shard slices ONE slot, selects between
        the new token and the existing row depending on ownership, and
        writes ONE slot back: O(B·KV·hd) traffic instead of O(cache).
      ring_write=False: one-hot masked select over the whole cache — the
        baseline (correct everywhere, 1 extra full cache read+write).
    """
    C = cache["k"].shape[1]
    slot = cache["ptr"] % C  # scalar
    if ring_write:
        shard_axes = seq_shard_axes()
        if shard_axes:
            return _ring_write_sharded(cache, k, v, positions, slot, shard_axes)
    hit = (jnp.arange(C, dtype=jnp.int32) == slot)  # (C,)
    kc = jnp.where(hit[None, :, None, None], k.astype(cache["k"].dtype), cache["k"])
    vc = jnp.where(hit[None, :, None, None], v.astype(cache["v"].dtype), cache["v"])
    pc = jnp.where(hit[None, :], positions, cache["pos"])
    return {"k": kc, "v": vc, "pos": pc, "ptr": cache["ptr"] + 1}


def kv_cache_write_chunk(cache, row, k, v, positions, ax: int = 0):
    """Write one row's chunk of entries as that many `kv_cache_append`
    calls in which only ``row`` is live would: entry i at ring slot
    (ptr + i) mod capacity, every other row's entry at those slots empty
    (-1), the cursor advanced by the number of valid entries.  Padding
    (position -1) writes nothing.  ``ax`` is the batch axis (1 for a
    unit-stacked cache); k/v (…, 1, T, KV, hd) with the row at ``ax``,
    positions (T,).  Other rows' K/V at those slots are left as they
    are: position -1 hides them.

    Each slot of the row's ring takes its entry (or keeps its own), and
    the row goes back whole: a dynamic-update-slice along the batch axis,
    in place when the cache is donated, whatever the ring's layout."""
    B, C = cache["pos"].shape[ax:ax + 2]
    T = positions.shape[0]
    n = jnp.sum(positions >= 0, dtype=jnp.int32)
    ptr = jnp.ravel(cache["ptr"])[0]  # every layer's cursor is the same
    i = (jnp.arange(C, dtype=jnp.int32) - ptr) % C  # each slot's entry index
    put = i < n
    src = jnp.minimum(i, T - 1)
    z = jnp.zeros((), jnp.int32)
    out = {"ptr": cache["ptr"] + n}
    for name, new in (("k", k), ("v", v)):
        at = (z,) * ax + (row,) + (z,) * (new.ndim - ax - 1)
        old = jax.lax.dynamic_slice_in_dim(cache[name], row, 1, ax)
        val = jnp.take(new, src, axis=ax + 1).astype(old.dtype)
        out[name] = jax.lax.dynamic_update_slice(
            cache[name], jnp.where(put[:, None, None], val, old), at)
    mine = jnp.where(jnp.arange(B)[:, None] == row, positions[src], -1)
    out["pos"] = jnp.where(put, mine, cache["pos"])
    return out


def _ring_write_sharded(cache, k, v, positions, slot, shard_axes):
    """Owning-shard single-slot write under shard_map (manual over the
    cache's sequence-sharding axes, auto elsewhere)."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    seq_spec = tuple(shard_axes) if len(shard_axes) > 1 else shard_axes[0]

    def body(kc, vc, pc, kn, vn, pn, slot_):
        # local views: kc (B, C_local, KV, hd); compute the local slot
        idx = jax.lax.axis_index(shard_axes[0])
        for a in shard_axes[1:]:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        c_local = kc.shape[1]
        local = slot_ - idx * c_local
        owned = (local >= 0) & (local < c_local)
        li = jnp.clip(local, 0, c_local - 1)
        z = jnp.zeros((), jnp.int32)
        # read one slot, select, write one slot — O(token) traffic
        cur_k = jax.lax.dynamic_slice(kc, (z, li, z, z), (kc.shape[0], 1) + kc.shape[2:])
        cur_v = jax.lax.dynamic_slice(vc, (z, li, z, z), (vc.shape[0], 1) + vc.shape[2:])
        cur_p = jax.lax.dynamic_slice(pc, (z, li), (pc.shape[0], 1))
        new_k = jnp.where(owned, kn.astype(kc.dtype), cur_k)
        new_v = jnp.where(owned, vn.astype(vc.dtype), cur_v)
        new_p = jnp.where(owned, pn, cur_p)
        return (
            jax.lax.dynamic_update_slice(kc, new_k, (z, li, z, z)),
            jax.lax.dynamic_update_slice(vc, new_v, (z, li, z, z)),
            jax.lax.dynamic_update_slice(pc, new_p, (z, li)),
        )

    kv_spec = P(None, seq_spec, None, None)
    pos_spec = P(None, seq_spec)
    rep4 = P(None, None, None, None)
    kc, vc, pc = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(kv_spec, kv_spec, pos_spec, rep4, rep4, P(None, None), P()),
        out_specs=(kv_spec, kv_spec, pos_spec),
        axis_names=set(shard_axes),
        check_vma=False,
    )(cache["k"], cache["v"], cache["pos"], k, v, positions, slot)
    return {"k": kc, "v": vc, "pos": pc, "ptr": cache["ptr"] + 1}


# ----------------------------------------------------- recurrent states ----


def make_mlstm_state(batch, n_heads, d_k, d_v, d_conv, conv_k=4, dtype=jnp.float32, conv_dtype=jnp.float32):
    return {
        "C": jnp.zeros((batch, n_heads, d_k, d_v), dtype),  # matrix memory
        "n": jnp.zeros((batch, n_heads, d_k), dtype),  # normalizer
        "m": jnp.zeros((batch, n_heads), dtype),  # stabilizer (log-space)
        "conv": jnp.zeros((batch, conv_k - 1, d_conv), conv_dtype),  # causal-conv tail
    }


def make_slstm_state(batch, n_heads, head_dim, dtype=jnp.float32):
    return {
        "c": jnp.zeros((batch, n_heads, head_dim), dtype),
        "n": jnp.zeros((batch, n_heads, head_dim), dtype),
        "h": jnp.zeros((batch, n_heads, head_dim), dtype),
        "m": jnp.zeros((batch, n_heads, head_dim), dtype),
    }


def make_rglru_state(batch, width, conv_k=4, dtype=jnp.float32, conv_dtype=jnp.float32):
    return {
        "h": jnp.zeros((batch, width), dtype),
        "conv": jnp.zeros((batch, conv_k - 1, width), conv_dtype),  # causal-conv tail
    }


def make_mamba2_state(batch, n_heads, head_dim, d_state, conv_dim, conv_k=4, dtype=jnp.float32):
    return {
        "ssm": jnp.zeros((batch, n_heads, head_dim, d_state), dtype),  # per head P × N
        "conv": jnp.zeros((batch, conv_k - 1, conv_dim), dtype),  # causal-conv tail
    }
