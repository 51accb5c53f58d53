"""Pallas TPU kernel: ragged blockwise flash-PREFILL over a block-paged KV
pool — the chunked-prefill counterpart of `kernels/paged_decode`.

One launch serves every slot's prompt chunk of the round: slot s has
``lens[s]`` new tokens starting at absolute position ``off[s]`` (= tokens
already in its pool blocks), and the kernel

  * **writes the chunk's K/V into the freshly-taken pool blocks in the
    same pass** — the pool arrays are aliased in/out
    (``input_output_aliases``), and each grid step that overlaps the
    chunk window merges the chunk rows into the block it just fetched
    (`ref.paged_prefill_merge` — a 0/1 one-hot matmul, exact in f32 and
    MXU-shaped, instead of an in-kernel dynamic gather) before writing it
    back through a table-driven output index map;
  * computes **causal-within-chunk + full attention to all prior pool
    blocks** for the chunk queries over exactly the blocks the slot holds
    — the online-softmax recurrence is `ref.flash_prefill_block`, shared
    VERBATIM with the oracle `ref.paged_prefill_ref`, so interpret-mode
    bit-exactness pins the paging/writeback logic, not fp reassociation.

TPU adaptation notes:
  * grid = (S, KV, MB), block axis innermost-sequential; block table,
    chunk offsets, and chunk lengths ride in as scalar prefetch
    (`pltpu.PrefetchScalarGridSpec`) so both the K/V **input** index maps
    (``tbl[s, i]``, −1 clamped to the trash block) and the **output**
    index maps (the merged block's id when the step overlaps the chunk,
    the trash block otherwise) are data-driven;
  * the pools are padded with one TRASH block (index NB): non-writing
    grid steps aim their mandatory output copy there, so aliased pool
    content is mutated only by each block's owning slot — the engine's
    no-aliasing invariant makes the writes race-free;
  * raggedness: blocks at or past a slot's written range
    (``i·BS ≥ off+len``) and idle slots (``len == 0``) are skipped with
    `pl.when` — empty rounds cost no flops;
  * GQA: the G q-heads of a kv head are stacked into the q block rows
    (group-major, `flash_attention`'s trick), each row masked by its own
    chunk position — one program per (slot, kv head).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import NEG_INF, flash_prefill_block, paged_prefill_merge


def _prefill_kernel(tbl_ref, off_ref, len_ref, q_ref, kc_ref, vc_ref,
                    kp_ref, vp_ref, o_ref, ko_ref, vo_ref,
                    acc_ref, m_ref, l_ref, *, scale, block_size, chunk_cap):
    s = pl.program_id(0)
    i = pl.program_id(2)
    BS = block_size

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    off = off_ref[s]
    ln = len_ref[s]

    @pl.when((i * BS < off + ln) & (ln > 0))  # ragged bound: skip dead blocks
    def _block():
        q = q_ref[0, 0]          # (G·CT, hd) — GQA groups stacked row-major
        # block-row token positions as a column (merge) and a row (mask)
        tcol = i * BS + jax.lax.broadcasted_iota(jnp.int32, (BS, 1), 0)
        trow = i * BS + jax.lax.broadcasted_iota(jnp.int32, (1, BS), 1)
        # merge this block's slice of the chunk K/V (freshly-taken blocks
        # get their rows here — the in-pass writeback), then attend over
        # the MERGED content: the partially-filled boundary block serves
        # both its old rows and the chunk's new ones in one fetch
        sel, ku = paged_prefill_merge(kc_ref[0, 0], tcol, off, ln)
        _, vu = paged_prefill_merge(vc_ref[0, 0], tcol, off, ln)
        kblk = jnp.where(sel, ku, kp_ref[0, 0])
        vblk = jnp.where(sel, vu, vp_ref[0, 0])
        ko_ref[0, 0] = kblk
        vo_ref[0, 0] = vblk
        rows = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], 1), 0) \
            % chunk_cap
        qpos = off + rows
        mask = (rows < ln) & (trow <= qpos)  # causal + ragged
        m, l, acc = flash_prefill_block(
            q, kblk, vblk, mask, m_ref[...], l_ref[...], acc_ref[...],
            scale=scale)
        m_ref[...] = m
        l_ref[...] = l
        acc_ref[...] = acc

    @pl.when(i == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill(q, k_chunk, v_chunk, k_pool, v_pool, block_tbl, off, lens,
                  *, interpret=False):
    """q: (S, CT, H, hd) chunk queries; k_chunk/v_chunk: (S, CT, KV, hd);
    k_pool/v_pool: (NB, BS, KV, hd); block_tbl: (S, MB) int32 (-1 ⇒
    unallocated); off: (S,) int32 chunk start positions; lens: (S,) int32
    chunk lengths (0 ⇒ idle slot).  Returns ``(out (S, CT, H, hd),
    k_pool', v_pool')`` with the chunk KV written into the slots' blocks.
    Oracle: `ref.paged_prefill_ref` (bit-exact in interpret mode)."""
    S, CT, H, hd = q.shape
    NB, BS, KV, _ = k_pool.shape
    MB = block_tbl.shape[1]
    assert H % KV == 0
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    qr = (q.reshape(S, CT, KV, G, hd).transpose(0, 2, 3, 1, 4)
          .reshape(S, KV, G * CT, hd))
    kc = k_chunk.transpose(0, 2, 1, 3)     # (S, KV, CT, hd)
    vc = v_chunk.transpose(0, 2, 1, 3)
    pad = ((0, 1), (0, 0), (0, 0), (0, 0))  # + the trash block (index NB)
    kp = jnp.pad(k_pool, pad).transpose(2, 0, 1, 3)  # (KV, NB+1, BS, hd)
    vp = jnp.pad(v_pool, pad).transpose(2, 0, 1, 3)
    tbl = jnp.asarray(block_tbl, jnp.int32)
    off = jnp.asarray(off, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)

    def kv_map(s, h, i, tbl_ref, off_ref, len_ref):
        # table-driven DMA: -1 (unallocated) clamps to pool block 0, same
        # as the oracle — compute for it is always masked/skipped (a
        # slot's written range never reaches an unallocated block)
        return (h, jnp.maximum(tbl_ref[s, i], 0), 0, 0)

    def wr_map(s, h, i, tbl_ref, off_ref, len_ref):
        # the mandatory per-step output copy lands on the merged block
        # only when this step overlaps the chunk window; everything else
        # (skipped steps, pure-attention steps over old blocks) goes to
        # the trash block, keeping aliased pool content owner-written
        o, ln = off_ref[s], len_ref[s]
        wr = (ln > 0) & (i * BS < o + ln) & (i * BS + BS > o)
        return (h, jnp.where(wr, jnp.maximum(tbl_ref[s, i], 0), NB), 0, 0)

    def q_map(s, h, i, tbl_ref, off_ref, len_ref):
        return (s, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, KV, MB),
        in_specs=[
            pl.BlockSpec((1, 1, G * CT, hd), q_map),
            pl.BlockSpec((1, 1, CT, hd), q_map),
            pl.BlockSpec((1, 1, CT, hd), q_map),
            pl.BlockSpec((1, 1, BS, hd), kv_map),
            pl.BlockSpec((1, 1, BS, hd), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G * CT, hd), q_map),
            pl.BlockSpec((1, 1, BS, hd), wr_map),
            pl.BlockSpec((1, 1, BS, hd), wr_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((G * CT, hd), jnp.float32),
            pltpu.VMEM((G * CT, 1), jnp.float32),
            pltpu.VMEM((G * CT, 1), jnp.float32),
        ],
    )
    out, kp2, vp2 = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, block_size=BS,
                          chunk_cap=CT),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((S, KV, G * CT, hd), q.dtype),
            jax.ShapeDtypeStruct((KV, NB + 1, BS, hd), k_pool.dtype),
            jax.ShapeDtypeStruct((KV, NB + 1, BS, hd), v_pool.dtype),
        ],
        input_output_aliases={6: 1, 7: 2},  # pools flow through, in-place
        interpret=interpret,
    )(tbl, off, lens, qr, kc, vc, kp, vp)
    out = (out.reshape(S, KV, G, CT, hd).transpose(0, 3, 1, 2, 4)
           .reshape(S, CT, H, hd))
    return (out, kp2.transpose(1, 2, 0, 3)[:NB],
            vp2.transpose(1, 2, 0, 3)[:NB])
