"""Pallas TPU kernel for the batched TWA semaphore pass — the paper's
take + post + waiting-array notification, executed for a whole vector of
requests in one VMEM-resident sweep.

This is the L2 adaptation of the paper (DESIGN.md): TPUs have no in-graph
shared-memory atomics, so the wait-free fetch_add linearization becomes a
deterministic batch linearization:

  * `fetch_add` per request  →  base + exclusive prefix rank.  Computed on
    the MXU as `req · strict_lower_triangle(1)` — a (block_n × block_n)
    masked matmul is both exact (counts ≪ 2²⁴ in f32) and systolic-friendly,
    instead of a sequential scan;
  * ticket issuance order == row order == FCFS — the paper's
    first-come-first-enabled admission, preserved batchwise;
  * the waiting array is a (T,) sequence vector in VMEM; the post side bumps
    the TWAHash buckets of the enabled ticket range [grant, grant+post_n) —
    because the stride 17 is coprime with T, a window of consecutive tickets
    is a *permutation* of bucket indices, implemented as an iota-compare
    one-hot reduction (VPU) rather than a scatter;
  * `woken` = requests whose bucket moved — the scheduler re-examines ONLY
    those rows next step (the kernel-level analogue of not globally
    spinning: O(woken) instead of O(waiters) re-checks).

Multi-block grids carry the running request count (the ticket counter) in a
scratch accumulator across the sequential grid axis, mirroring the single
atomic counter the CPU algorithm maintains.

Oracle: ref.sema_batch_ref (== core.functional semantics).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TICKET_STRIDE = 17


def _sema_kernel(scal_ref, req_ref, seq_ref, tickets_ref, admitted_ref,
                 bucket_ref, woken_ref, new_scal_ref, new_seq_ref,
                 base_ref, *, table, block_n):
    # scalars (ticket, grant, post_n, salt) and the running ticket base live
    # in SMEM; per-request rows are (1, block_n) lane vectors, and every
    # reduction runs in int32 (Mosaic reduces no unsigned integers)
    i = pl.program_id(0)
    grant = scal_ref[1]
    post_n = scal_ref[2]
    salt = scal_ref[3]

    @pl.when(i == 0)
    def _init():
        base_ref[0] = scal_ref[0]

    req_i = req_ref[...]  # (1, block_n) i32
    req = req_i.astype(jnp.float32)
    # exclusive prefix rank via strict-lower-triangular matmul (MXU):
    # ranks[0, r] = Σ_c tri[r, c]·req[0, c] = # requests before row r
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 1)
    tri = (cols < rows).astype(jnp.float32)
    ranks = jax.lax.dot_general(
        req, tri, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (1, block_n)
    base = base_ref[0]
    tickets = base + ranks.astype(jnp.int32).astype(jnp.uint32)
    reqb = req_i != 0
    admitted = reqb & ((grant - tickets).astype(jnp.int32) > 0)

    mask = jnp.uint32(table - 1)
    idx = ((salt + tickets * jnp.uint32(TICKET_STRIDE)) & mask).astype(jnp.int32)

    # post side: bump buckets of the enabled ticket range [grant, grant+n)
    offs = jax.lax.broadcasted_iota(jnp.int32, (table, 1), 0).astype(jnp.uint32)
    enabled = offs < post_n  # (T, 1)
    post_idx = ((salt + (grant + offs) * jnp.uint32(TICKET_STRIDE)) & mask
                ).astype(jnp.int32)
    # permutation one-hot reduction: bump[j] = Σ_i enabled[i]·[post_idx_i == j]
    tcols = jax.lax.broadcasted_iota(jnp.int32, (table, table), 1)
    bump = jnp.sum(jnp.where((post_idx == tcols) & enabled, 1, 0),
                   axis=0, keepdims=True)  # (1, T) i32
    new_seq = seq_ref[...] + bump.astype(jnp.uint32)

    # gather bump at each waiter's bucket (compare-select, no scatter/gather)
    brows = jax.lax.broadcasted_iota(jnp.int32, (table, block_n), 0)
    bump_at = jnp.sum(jnp.where(brows == idx, bump.T, 0), axis=0,
                      keepdims=True)  # (1, block_n)
    woken = reqb & (bump_at > 0)

    tickets_ref[...] = tickets
    admitted_ref[...] = admitted.astype(jnp.int32)
    bucket_ref[...] = idx
    woken_ref[...] = woken.astype(jnp.int32)
    new_seq_ref[...] = new_seq

    n_req = jnp.sum(req_i).astype(jnp.uint32)
    base_ref[0] = base + n_req

    @pl.when(i == pl.num_programs(0) - 1)
    def _fin():
        new_scal_ref[0] = base + n_req  # final ticket counter
        new_scal_ref[1] = grant + post_n
        new_scal_ref[2] = jnp.uint32(0)
        new_scal_ref[3] = salt


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def sema_batch(ticket, grant, bucket_seq, requests, post_n, salt,
               *, block_n: int = 512, interpret=False):
    """Fused batched semaphore pass.  requests: (N,) bool.
    Returns (new_ticket, new_grant, new_bucket_seq, tickets, admitted,
    bucket, woken)."""
    N = requests.shape[0]
    T = bucket_seq.shape[0]
    assert T & (T - 1) == 0
    block_n = min(block_n, max(N, 8))
    pad = (-N) % block_n
    reqp = jnp.pad(requests.astype(jnp.int32), (0, pad))
    nb = (N + pad) // block_n
    scal = jnp.stack([jnp.asarray(x, jnp.uint32) for x in (ticket, grant, post_n, salt)])

    outs = pl.pallas_call(
        functools.partial(_sema_kernel, table=T, block_n=block_n),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, T), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, T), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, N + pad), jnp.uint32),   # tickets
            jax.ShapeDtypeStruct((1, N + pad), jnp.int32),    # admitted
            jax.ShapeDtypeStruct((1, N + pad), jnp.int32),    # bucket
            jax.ShapeDtypeStruct((1, N + pad), jnp.int32),    # woken
            jax.ShapeDtypeStruct((4,), jnp.uint32),           # new scalars
            jax.ShapeDtypeStruct((1, T), jnp.uint32),         # new bucket_seq
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.uint32)],
        interpret=interpret,
    )(scal, reqp.reshape(1, -1), bucket_seq.reshape(1, -1))

    tickets, admitted, bucket, woken, new_scal, new_seq = outs
    return (
        new_scal[0],
        new_scal[1],
        new_seq[0],
        tickets[0, :N],
        admitted[0, :N].astype(bool),
        bucket[0, :N],
        woken[0, :N].astype(bool),
    )
