"""Pallas TPU kernel for the fused multi-tenant QoS admission round — the
whole expire → weighted stride replenish → tombstone-transparent FCFS admit →
reclaim pass of `admission/functional_qos.qos_round`, executed as ONE
VMEM-resident kernel over the backlog.

Structure (mirrors `kernels/sema_batch`'s blocked discipline; oracle:
`ref.qos_round_ref` == `functional_qos.qos_round`):

  * the backlog rows arrive pre-sorted by wrap-safe per-tenant ticket order
    (the argsort is XLA data prep in the wrapper; ranks never need ticket
    values inside the kernel, only the order);
  * grid = (2, nb): phase 0 sweeps the row blocks accumulating per-tenant
    live depth and expiry counts in VMEM scratch (the sequential-grid
    carry, exactly `sema_batch`'s running ticket base);
  * between the sweeps (first step of phase 1) the weighted replenishment
    is solved in CLOSED FORM: tenant s's k-th grant crosses virtual time
    vpass_s + k/w_s, so the stride schedule is the merge of S arithmetic
    sequences.  The kernel selects the first `take` crossings without a
    sort: a 32-step bit-descend over f32-bitcast keys finds the take-th
    smallest crossing, ties resolved in tenant order — bit-identical to
    the reference's stable argsort;
  * the waiting-array poke inverts the coprime ticket stride (17⁻¹ mod T)
    to turn each tenant's enabled window into a permutation-offset compare
    (`bump[j] = Σ_s [((j − start_s)·17⁻¹ mod T) < width_s]`) — no scatter;
  * phase 1 re-sweeps the row blocks: per-block per-tenant live ranks come
    from the MXU strict-lower-triangular matmul (the tri-rank trick) plus
    the carried (S,) alive-count base; admit ⇔ rank < replenished avail;
  * the last step reclaims credit stranded past live demand and decays the
    dead-below-frontier poke slack — final state written once.

O(N·S/block + S·max_units + S·T) work — the O(N²) pairwise rank and the
max_units-length sequential argmin loop of the pre-PR-2 reference are gone
on both paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..admission.functional_qos import STRIDE_INV
from ..core.functional import ticket_order, twa_hash_u32
from ..core.hashfn import MIX32KA

_INF_BITS = 0x7F800000  # f32 +inf bit pattern (crossings are ≥ 0)


def _col(mask):
    """(n,) bool → (n, 1) bool.  Mosaic cannot reshape a 1-bit vector
    into a column, so the mask makes the trip as int32."""
    return mask.astype(jnp.int32)[:, None] != 0


def _count(mask, axis):
    """Count of set entries along ``axis`` as uint32.  Mosaic reduces no
    unsigned integers, so the sum runs in int32; counts stay far below
    2³¹, so the value equals the uint32 sum and the caller's u32 add
    keeps its mod-2³² wrap."""
    return jnp.sum(mask.astype(jnp.int32), axis=axis).astype(jnp.uint32)


def _qos_kernel(scal_u_ref, scal_i_ref, nowf_ref, wf_ref, st_ref, seq_ref,
                ids_ref, alive_ref, dl_ref,
                adm_ref, exp_ref, out_u_ref, out_vp_ref, out_seq_ref,
                out_scal_ref,
                depth_ref, deadb_ref, alloc_ref, availr_ref, carry_ref,
                spent_ref, *, table, block_n, s_pad, max_units, u_pad):
    p = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    salt = scal_u_ref[0]
    free = scal_i_ref[0]
    now = nowf_ref[0]

    ids = ids_ref[0]  # (block_n,) i32, rows pre-sorted by ticket order
    alive_in = alive_ref[0] != 0
    newly = alive_in & (dl_ref[0] <= now)  # deadline-expired this round
    alive2 = alive_in & ~newly

    scols = jax.lax.broadcasted_iota(jnp.int32, (block_n, s_pad), 1)
    onehot = scols == ids[:, None]  # (block_n, Sp)
    oh_alive = onehot & _col(alive2)
    cnt_alive = jnp.sum(oh_alive.astype(jnp.int32), axis=0)  # (Sp,)

    @pl.when((p == 0) & (j == 0))
    def _init():
        depth_ref[0] = jnp.zeros((s_pad,), jnp.int32)
        deadb_ref[0] = jnp.zeros((s_pad,), jnp.uint32)

    @pl.when(p == 0)
    def _sweep_depth():
        depth_ref[0] = depth_ref[0] + cnt_alive
        deadb_ref[0] = deadb_ref[0] + _count(onehot & _col(newly), 0)
        # every output block is fully written each visit (revisited at p=1)
        adm_ref[0] = jnp.zeros((block_n,), jnp.int32)
        exp_ref[0] = newly.astype(jnp.int32)

    @pl.when((p == 1) & (j == 0))
    def _replenish():
        weight = wf_ref[0]
        vpass = wf_ref[1]
        grant = st_ref[1]
        consumed = st_ref[2]
        avail0 = (grant - consumed).astype(jnp.int32)
        unmet = jnp.clip(depth_ref[0] - avail0, 0, max_units)

        # crossing matrix: value of tenant s's k-th grant in virtual time
        kf = jax.lax.broadcasted_iota(jnp.int32, (s_pad, u_pad), 1).astype(
            jnp.float32)
        step = jnp.where(weight[:, None] > 0, kf / weight[:, None], jnp.inf)
        step = jnp.where(kf == 0, 0.0, step)  # k=0 crossing is vpass itself
        cross = jnp.where(kf < unmet[:, None].astype(jnp.float32),
                          vpass[:, None] + step, jnp.inf)
        key = jax.lax.bitcast_convert_type(cross, jnp.uint32)
        finite = key < jnp.uint32(_INF_BITS)  # crossings ≥ 0 ⇒ bits monotone
        take = jnp.minimum(
            jnp.minimum(jnp.maximum(free, 0), jnp.int32(max_units)),
            jnp.sum(finite.astype(jnp.int32)))

        # bit-descend: largest θ with count_lt(θ) < take == take-th smallest
        def bit_body(b, theta):
            cand = theta | (jnp.uint32(1) << (jnp.uint32(31) - b.astype(jnp.uint32)))
            cnt_lt = jnp.sum((key < cand).astype(jnp.int32))
            return jnp.where(cnt_lt < take, cand, theta)

        theta = jax.lax.fori_loop(0, 32, bit_body, jnp.uint32(0))
        lt = key < theta
        eq = key == theta
        rem = take - jnp.sum(lt.astype(jnp.int32))
        lt_s = jnp.sum(lt.astype(jnp.int32), axis=1)
        eq_s = jnp.sum(eq.astype(jnp.int32), axis=1)
        # tie units flow in tenant-index order (== the reference's stable
        # argsort over the row-major crossing matrix): exclusive prefix of
        # eq via the strict-lower-triangular MXU matmul
        rows = jax.lax.broadcasted_iota(jnp.int32, (s_pad, s_pad), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (s_pad, s_pad), 1)
        tri = (cols < rows).astype(jnp.float32)
        exc = jax.lax.dot_general(
            tri, eq_s.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
        extra = jnp.clip(rem - exc, 0, eq_s)
        alloc_i = lt_s + extra
        alloc = alloc_i.astype(jnp.uint32)

        alloc_ref[0] = alloc
        availr_ref[0] = avail0 + alloc_i
        af = alloc_i.astype(jnp.float32)
        out_vp_ref[0] = vpass + jnp.where(
            alloc > 0, jnp.where(weight > 0, af / weight, jnp.inf), 0.0)

        # waiting-array poke: enabled window [grant_s, grant_s + width_s),
        # width = alloc + not-yet-reclaimed dead slack, clamped to the
        # issued-ticket frontier; coprime-stride inversion instead of a
        # hash-index scatter
        dead0 = st_ref[3] + deadb_ref[0]
        outstanding = jnp.maximum((st_ref[0] - grant).astype(jnp.int32), 0)
        width = jnp.minimum((alloc + dead0).astype(jnp.int32),
                            outstanding).astype(jnp.uint32)
        jcols = jax.lax.broadcasted_iota(jnp.uint32, (s_pad, table), 1)
        srows = jax.lax.broadcasted_iota(jnp.uint32, (s_pad, table), 0)
        tsalt = salt + (srows + 1) * jnp.uint32(MIX32KA)  # == tenant_salt
        start = twa_hash_u32(tsalt, grant[:, None])
        offs = ((jcols - start) * jnp.uint32(STRIDE_INV)) & jnp.uint32(table - 1)
        out_seq_ref[0] = seq_ref[0] + _count(offs < width[:, None], 0)

        carry_ref[0] = jnp.zeros((s_pad,), jnp.int32)
        spent_ref[0] = jnp.zeros((s_pad,), jnp.uint32)

    @pl.when(p == 1)
    def _sweep_admit():
        # per-tenant exclusive live rank within the block (tri-rank on MXU)
        rows_b = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 0)
        cols_b = jax.lax.broadcasted_iota(jnp.int32, (block_n, block_n), 1)
        trib = (cols_b < rows_b).astype(jnp.float32)
        pre = jax.lax.dot_general(
            trib, oh_alive.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # (block_n, Sp)
        base = carry_ref[0]
        rank = jnp.sum(
            jnp.where(onehot, pre.astype(jnp.int32) + base[None, :], 0), axis=1)
        my_avail = jnp.sum(jnp.where(onehot, availr_ref[0][None, :], 0), axis=1)
        admitted = alive2 & (rank < my_avail)
        adm_ref[0] = admitted.astype(jnp.int32)
        exp_ref[0] = newly.astype(jnp.int32)
        carry_ref[0] = base + cnt_alive
        spent_ref[0] = spent_ref[0] + _count(onehot & _col(admitted), 0)

    @pl.when((p == 1) & (j == nb - 1))
    def _fin():
        grant = st_ref[1]
        alloc = alloc_ref[0]
        spent = spent_ref[0]
        dead0 = st_ref[3] + deadb_ref[0]
        depth_after = depth_ref[0] - spent.astype(jnp.int32)
        avail_after = availr_ref[0] - spent.astype(jnp.int32)
        surplus = jnp.maximum(avail_after - depth_after, 0).astype(jnp.uint32)
        out_u_ref[0] = grant + alloc
        out_u_ref[1] = st_ref[2] + spent + surplus
        # frontier decay: dead0 − min(dead0, surplus), written as a select
        # because Mosaic has no unsigned min
        out_u_ref[2] = jnp.where(dead0 > surplus, dead0 - surplus,
                                 jnp.uint32(0))
        out_u_ref[3] = alloc
        leftover = (free - jnp.sum(alloc.astype(jnp.int32))
                    + jnp.sum(surplus.astype(jnp.int32)))
        out_scal_ref[0] = leftover


def _roundup(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit,
                   static_argnames=("max_units", "block_n", "interpret"))
def qos_round_fused(state, tenant_ids, tickets, alive, deadlines, now,
                    free_units, *, max_units: int, block_n: int = 256,
                    interpret: bool = False):
    """Fused multi-tenant admission round (kernel counterpart of
    `functional_qos.qos_round`).  Returns
    ``(state', admitted, expired, leftover)`` — bit-identical to the
    reference in interpret mode.

    The per-tenant ticket-order argsort (wrap-safe: keys are signed
    distances from each tenant's first-seen ticket) runs as XLA data prep;
    everything else — both row sweeps, the closed-form stride allocation,
    the permutation poke — is one `pallas_call` over a (2, nb) grid.
    """
    N = tenant_ids.shape[0]
    S = state.ticket.shape[0]
    T = state.bucket_seq.shape[-1]
    tenant_ids = jnp.asarray(tenant_ids, jnp.int32)
    tickets = jnp.asarray(tickets, jnp.uint32)
    alive = jnp.asarray(alive, bool)
    deadlines = jnp.asarray(deadlines, jnp.float32)

    # wrap-safe per-tenant ticket-order sort — MUST be the same permutation
    # the reference rank path uses (bit-exactness), hence the shared helper
    order = ticket_order(tenant_ids, tickets, S)

    block_n = min(block_n, _roundup(max(N, 8), 8))
    pad = max(_roundup(N, block_n), block_n) - N  # ≥ 1 block even for N=0
    nb = (N + pad) // block_n
    ids_p = jnp.pad(tenant_ids[order], (0, pad))
    alive_p = jnp.pad(alive[order], (0, pad))
    dl_p = jnp.pad(deadlines[order], (0, pad), constant_values=jnp.inf)

    s_pad = _roundup(S, 128)
    u_pad = _roundup(max_units, 128)
    zpad = (0, s_pad - S)
    wf = jnp.stack([jnp.pad(state.weight, zpad), jnp.pad(state.vpass, zpad)])
    st = jnp.stack([jnp.pad(x, zpad) for x in
                    (state.ticket, state.grant, state.consumed, state.dead)])
    scal_u = jnp.zeros((8,), jnp.uint32).at[0].set(
        jnp.asarray(state.salt, jnp.uint32))
    scal_i = jnp.zeros((8,), jnp.int32).at[0].set(
        jnp.asarray(free_units, jnp.int32))
    nowf = jnp.zeros((8,), jnp.float32).at[0].set(
        jnp.asarray(now, jnp.float32))

    outs = pl.pallas_call(
        functools.partial(_qos_kernel, table=T, block_n=block_n, s_pad=s_pad,
                          max_units=max_units, u_pad=u_pad),
        grid=(2, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((2, s_pad), lambda p, j: (0, 0)),
            pl.BlockSpec((4, s_pad), lambda p, j: (0, 0)),
            pl.BlockSpec((1, T), lambda p, j: (0, 0)),
            pl.BlockSpec((1, block_n), lambda p, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda p, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda p, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda p, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda p, j: (0, j)),
            pl.BlockSpec((4, s_pad), lambda p, j: (0, 0)),
            pl.BlockSpec((1, s_pad), lambda p, j: (0, 0)),
            pl.BlockSpec((1, T), lambda p, j: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, N + pad), jnp.int32),   # admitted
            jax.ShapeDtypeStruct((1, N + pad), jnp.int32),   # expired
            jax.ShapeDtypeStruct((4, s_pad), jnp.uint32),    # grant/cons/dead/alloc
            jax.ShapeDtypeStruct((1, s_pad), jnp.float32),   # vpass
            jax.ShapeDtypeStruct((1, T), jnp.uint32),        # bucket_seq
            jax.ShapeDtypeStruct((8,), jnp.int32),           # leftover
        ],
        scratch_shapes=[
            pltpu.VMEM((1, s_pad), jnp.int32),    # depth
            pltpu.VMEM((1, s_pad), jnp.uint32),   # dead bump
            pltpu.VMEM((1, s_pad), jnp.uint32),   # alloc
            pltpu.VMEM((1, s_pad), jnp.int32),    # avail after replenish
            pltpu.VMEM((1, s_pad), jnp.int32),    # live-rank carry
            pltpu.VMEM((1, s_pad), jnp.uint32),   # admitted spend
        ],
        interpret=interpret,
    )(scal_u, scal_i, nowf, wf, st, state.bucket_seq.reshape(1, -1),
      ids_p.reshape(1, -1), alive_p.astype(jnp.int32).reshape(1, -1),
      dl_p.reshape(1, -1))

    adm_s, exp_s, out_u, out_vp, out_seq, out_scal = outs
    admitted = jnp.zeros((N,), bool).at[order].set(adm_s[0, :N] != 0)
    expired = jnp.zeros((N,), bool).at[order].set(exp_s[0, :N] != 0)
    new_state = state._replace(
        grant=out_u[0, :S], consumed=out_u[1, :S], dead=out_u[2, :S],
        vpass=out_vp[0, :S], bucket_seq=out_seq[0])
    return new_state, admitted, expired, out_scal[0]


@functools.partial(jax.jit,
                   static_argnames=("max_units", "block_n", "interpret"))
def qos_round_scan(state, tenant_ids, tickets, alive, deadlines, nows,
                   free_units, released, *, max_units: int,
                   block_n: int = 256, interpret: bool = False):
    """Batch-of-rounds entry point: K fused admission rounds as ONE jitted
    `lax.scan` over the kernel, with static padded shapes throughout — the
    megastep's admission spine (oracle: `ref.qos_round_scan_ref`, i.e. K
    sequential `functional_qos.qos_round` calls — bit-identical).

    Per round k: rows admitted/expired leave the alive set for round k+1;
    ``released[k]`` units (slot completions/preemptions fed back by the
    engine) join the carried free pool BEFORE the round's replenish (the
    `functional_qos.qos_scan_round` feedback contract); the leftover pool
    carries.  ``nows``: (K,) f32.  Returns ``(state', admit_round (N,)
    i32, expire_round (N,) i32, free')`` where round indices are -1 for
    rows never admitted/expired.
    """
    N = tenant_ids.shape[0]
    nows = jnp.asarray(nows, jnp.float32)
    released = jnp.asarray(released, jnp.int32)
    alive = jnp.asarray(alive, bool)
    free0 = jnp.asarray(free_units, jnp.int32)

    def body(carry, x):
        st, aliv, free = carry
        now, rel = x
        st, adm, exp, leftover = qos_round_fused(
            st, tenant_ids, tickets, aliv, deadlines, now, free + rel,
            max_units=max_units, block_n=block_n, interpret=interpret)
        return (st, aliv & ~adm & ~exp, leftover), (adm, exp)

    (state, _, free), (adm_k, exp_k) = jax.lax.scan(
        body, (state, alive, free0), (nows, released))
    # (K, N) event masks → first (only) round index per row, -1 if never
    admit_round = jnp.where(adm_k.any(0), jnp.argmax(adm_k, axis=0), -1)
    expire_round = jnp.where(exp_k.any(0), jnp.argmax(exp_k, axis=0), -1)
    return state, admit_round.astype(jnp.int32), \
        expire_round.astype(jnp.int32), free
