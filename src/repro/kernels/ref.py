"""Pure-jnp oracles for every Pallas kernel (the ground truth the kernels are
allclose-validated against, shape/dtype-swept in tests/test_kernels.py)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

MASK32 = (1 << 32) - 1
TICKET_STRIDE = 17


# ------------------------------------------------------------ sema_batch ----


def sema_batch_ref(ticket, grant, bucket_seq, requests, post_n, salt):
    """Oracle for the fused batched semaphore pass (the paper's take+post+
    notify adapted to a vector of K requests — see core/functional.py).

    Inputs (all jnp):
      ticket, grant: uint32 scalars     bucket_seq: (T,) uint32
      requests: (N,) bool               post_n: uint32 scalar
      salt: uint32 scalar (semaphore identity — uintptr_t(L) of TWAHash)

    Returns dict with new ticket/grant/bucket_seq, per-row tickets, admitted
    mask, bucket index, and woken mask (bucket moved this pass).
    """
    T = bucket_seq.shape[0]
    req = requests.astype(jnp.uint32)
    ranks = jnp.cumsum(req) - req
    tickets = ticket + ranks
    admitted = requests & ((grant - tickets).astype(jnp.int32) > 0)
    new_ticket = ticket + jnp.sum(req)

    idx = ((salt + tickets * jnp.uint32(TICKET_STRIDE)) & jnp.uint32(T - 1)).astype(jnp.int32)

    # post: grant advances by post_n; the enabled ticket range's buckets bump
    offs = jnp.arange(T, dtype=jnp.uint32)
    enabled = offs < post_n
    post_idx = ((salt + (grant + offs) * jnp.uint32(TICKET_STRIDE)) & jnp.uint32(T - 1)).astype(jnp.int32)
    bump = jnp.zeros((T,), jnp.uint32).at[post_idx].add(enabled.astype(jnp.uint32))
    new_seq = bucket_seq + bump
    woken = requests & (new_seq[idx] != bucket_seq[idx])
    return {
        "ticket": new_ticket,
        "grant": grant + post_n,
        "bucket_seq": new_seq,
        "tickets": tickets,
        "admitted": admitted,
        "bucket": idx,
        "woken": woken,
    }


# ------------------------------------------------------------- qos_round ----


def qos_round_ref(state, tenant_ids, tickets, alive, deadlines, now,
                  free_units, max_units: int):
    """Oracle for the fused multi-tenant QoS admission round — delegates to
    `admission.functional_qos.qos_round` (the reference semantics the
    `kernels/qos_admission` Pallas kernel must match bit-exactly: expire →
    weighted stride replenish → tombstone-transparent FCFS admit → reclaim).

    Returns dict with the new QoSState and per-row admitted/expired masks
    plus the leftover (work-conserving) unit count.
    """
    from ..admission.functional_qos import qos_round

    state2, admitted, expired, leftover = qos_round(
        state, tenant_ids, tickets, alive, deadlines, now, free_units,
        max_units)
    return {
        "state": state2,
        "admitted": admitted,
        "expired": expired,
        "leftover": leftover,
    }


def qos_round_scan_ref(state, tenant_ids, tickets, alive, deadlines, nows,
                       free_units, released, max_units: int):
    """Oracle for the batch-of-rounds scan (`kernels.qos_admission.
    qos_round_scan`): K sequential `functional_qos.qos_round` calls — each
    round's admitted/expired rows leave the alive set, each round's
    released units join the pool before its replenish, and the leftover
    pool carries.  Returns dict with the final state, per-row
    admit/expire round indices (-1 = never), and the final free pool."""
    from ..admission.functional_qos import qos_scan_round

    n = tickets.shape[0]
    alive = jnp.asarray(alive, bool)
    free = jnp.asarray(free_units, jnp.int32)
    admit_round = np.full(n, -1, np.int32)
    expire_round = np.full(n, -1, np.int32)
    for k in range(len(nows)):
        state, adm, exp, free = qos_scan_round(
            state, tenant_ids, tickets, alive, deadlines, nows[k], free,
            released[k], max_units)
        adm_np, exp_np = np.asarray(adm), np.asarray(exp)
        admit_round[adm_np] = k
        expire_round[exp_np] = k
        alive = alive & ~adm & ~exp
    return {
        "state": state,
        "admit_round": jnp.asarray(admit_round),
        "expire_round": jnp.asarray(expire_round),
        "free": free,
    }


# -------------------------------------------------------- flash attention ---


def mha_ref(q, k, v, *, causal=True, window=0, q_offset=0):
    """Naive O(S²) attention oracle. q: (B,Sq,H,hd); k/v: (B,Sk,KV,hd).
    GQA by head repetition; fp32 softmax; q_offset = absolute position of
    q row 0 (so a decode/step query can attend to a longer prefix)."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    group = H // KV
    kh = jnp.repeat(k, group, axis=2)
    vh = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kh.astype(jnp.float32))
    s *= 1.0 / math.sqrt(hd)
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    d = qpos[:, None] - kpos[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= d >= 0
    if window > 0:
        mask &= d < window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vh.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------- paged decode ----


NEG_INF = float("-inf")


def flash_decode_block(q, k, v, mask, m_prev, l_prev, acc_prev, *, scale):
    """One online-softmax block step of flash-decode — shared VERBATIM by
    the Pallas kernel (`kernels/paged_decode._paged_kernel`) and the
    blockwise oracle below, so interpret-mode bit-exactness tests the
    kernel's *paging* logic (table-driven DMA, ragged skip, init/finalize)
    rather than fp reassociation noise.

    q: (G, hd); k/v: (BS, hd); mask: (BS,) bool (valid tokens);
    m/l: (G, 1) f32 carries; acc: (G, hd) f32.  Returns (m', l', acc')."""
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale  # (G, BS)
    s = jnp.where(mask[None, :], s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(mask[None, :], jnp.exp(s - m_safe), 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_prev * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def paged_decode_ref(q, k_pool, v_pool, block_tbl, lens):
    """Blockwise oracle for the ragged paged-decode kernel
    (`kernels/paged_decode.paged_decode` — bit-exact in interpret mode).

    q: (S, H, hd); k_pool/v_pool: (NB, BS, KV, hd) — the shared block-paged
    KV pool; block_tbl: (S, MB) int32 block ids (-1 ⇒ unallocated);
    lens: (S,) int32 — valid tokens of each slot (tokens 0..len-1 live at
    block ``block_tbl[s, t // BS]`` offset ``t % BS``).  Returns (S, H, hd).

    The recurrence mirrors the kernel exactly (same `flash_decode_block`,
    same -1→0 table clamp, same ``i·BS < len`` ragged skip), and rows run
    under `lax.map` so every dot keeps the kernel's UNBATCHED (G, hd) ×
    (BS, hd) shape — a vmapped/batched dot reduces in a different order on
    CPU at G=1 (1-ulp drift) and would break the bit-exact contract.
    Semantic equivalence to the naive dense softmax is checked separately
    against `decode_attention_ref` over the gathered cache
    (tests/test_paged_decode.py) — the fp delta between blockwise and
    dense softmax is tiny but nonzero, so *bit*-exactness is defined
    against this blockwise form.
    """
    S, H, hd = q.shape
    NB, BS, KV, _ = k_pool.shape
    MB = block_tbl.shape[1]
    G = H // KV
    R = S * KV
    scale = 1.0 / math.sqrt(hd)
    qr = q.reshape(S, KV, G, hd).reshape(R, G, hd)
    kp = k_pool.transpose(2, 0, 1, 3)  # (KV, NB, BS, hd)
    vp = v_pool.transpose(2, 0, 1, 3)
    tbl_r = jnp.repeat(jnp.asarray(block_tbl, jnp.int32), KV, axis=0)
    lens_r = jnp.repeat(jnp.asarray(lens, jnp.int32), KV)
    head = jnp.tile(jnp.arange(KV, dtype=jnp.int32), S)  # r = s·KV + h

    def row(args):
        qrow, trow, ln, h = args
        m = jnp.full((G, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((G, 1), jnp.float32)
        acc = jnp.zeros((G, hd), jnp.float32)

        def body(carry, i):
            m, l, acc = carry
            b = jnp.maximum(trow[i], 0)          # the kernel's index-map clamp
            tpos = i * BS + jnp.arange(BS, dtype=jnp.int32)
            m2, l2, acc2 = flash_decode_block(
                qrow, kp[h, b], vp[h, b], tpos < ln, m, l, acc, scale=scale)
            upd = i * BS < ln                    # the kernel's pl.when skip
            return (jnp.where(upd, m2, m), jnp.where(upd, l2, l),
                    jnp.where(upd, acc2, acc)), None

        (m, l, acc), _ = jax.lax.scan(body, (m, l, acc),
                                      jnp.arange(MB, dtype=jnp.int32))
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    o = jax.lax.map(row, (qr, tbl_r, lens_r, head))
    return o.reshape(S, KV, G, hd).reshape(S, H, hd)


def paged_prefill_merge(chunk, tpos, off, length):
    """Merge the chunk rows that land in one pool block — shared VERBATIM
    by the Pallas kernel (`kernels/paged_prefill._prefill_kernel`) and the
    blockwise oracle `paged_prefill_ref`, so bit-exactness pins the
    writeback logic, not fp noise.

    ``chunk``: (CT, hd) this slot's chunk K or V rows; ``tpos``: (BS, 1)
    i32 absolute token positions of the block rows, as a column (Mosaic
    cannot turn a 1-bit vector into one); ``off``/``length``: chunk start
    position / token count.  Block row t receives chunk row
    ``tpos[t] − off`` iff it falls inside the chunk window.  The gather is
    a 0/1 one-hot matmul — MXU-friendly on TPU and EXACT in f32 (each
    output row is a single product with a 1.0) — instead of a dynamic
    in-kernel gather.  Returns ``(sel (BS, 1) bool, upd (BS, hd))``."""
    CT = chunk.shape[0]
    sel = (tpos >= off) & (tpos < off + length)
    c = tpos - off
    onehot = ((c == jax.lax.broadcasted_iota(jnp.int32, (1, CT), 1))
              & sel).astype(jnp.float32)
    upd = jax.lax.dot_general(
        onehot, chunk.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return sel, upd.astype(chunk.dtype)


def flash_prefill_block(q, k, v, mask, m_prev, l_prev, acc_prev, *, scale):
    """One online-softmax block step of blockwise flash-PREFILL — the 2-D
    masked sibling of :func:`flash_decode_block` (per-query-row masks:
    causal within the chunk, full attention to prior pool blocks), shared
    VERBATIM by `kernels/paged_prefill` and `paged_prefill_ref`.

    q: (Q, hd) chunk queries (GQA groups stacked row-major); k/v: (BS, hd);
    mask: (Q, BS) bool; m/l: (Q, 1) f32 carries; acc: (Q, hd) f32."""
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale  # (Q, BS)
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_prev * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def paged_prefill_ref(q, k_chunk, v_chunk, k_pool, v_pool, block_tbl, off,
                      lens):
    """Blockwise oracle for the ragged chunked-prefill kernel
    (`kernels/paged_prefill.paged_prefill` — bit-exact in interpret mode).

    q: (S, CT, H, hd) chunk queries; k_chunk/v_chunk: (S, CT, KV, hd) the
    chunk's new KV rows; k_pool/v_pool: (NB, BS, KV, hd); block_tbl:
    (S, MB) i32 (-1 ⇒ unallocated); off: (S,) i32 chunk start positions
    (= tokens already in the pool); lens: (S,) i32 chunk lengths (0 ⇒ slot
    idle this round).  Token t of slot s lives at block
    ``block_tbl[s, t // BS]`` offset ``t % BS``; blocks covering
    ``[0, off+len)`` must be allocated (the incremental allocator's
    invariant).

    Returns ``(out (S, CT, H, hd), k_pool', v_pool')`` — chunk KV merged
    into its freshly-taken blocks (`paged_prefill_merge`), and each chunk
    query attending causally within the chunk and fully to all prior
    tokens (`flash_prefill_block` over the block tables, same -1→0 clamp
    and ``i·BS < off+len`` ragged skip as the kernel; `lax.map` rows keep
    the kernel's unbatched dot shapes — see `paged_decode_ref` on why)."""
    S, CT, H, hd = q.shape
    NB, BS, KV, _ = k_pool.shape
    MB = block_tbl.shape[1]
    G = H // KV
    R = S * KV
    scale = 1.0 / math.sqrt(hd)
    qr = (q.reshape(S, CT, KV, G, hd).transpose(0, 2, 3, 1, 4)
          .reshape(R, G * CT, hd))
    kc = k_chunk.transpose(0, 2, 1, 3).reshape(R, CT, hd)
    vc = v_chunk.transpose(0, 2, 1, 3).reshape(R, CT, hd)
    kp = k_pool.transpose(2, 0, 1, 3)  # (KV, NB, BS, hd)
    vp = v_pool.transpose(2, 0, 1, 3)
    tbl_r = jnp.repeat(jnp.asarray(block_tbl, jnp.int32), KV, axis=0)
    off_r = jnp.repeat(jnp.asarray(off, jnp.int32), KV)
    len_r = jnp.repeat(jnp.asarray(lens, jnp.int32), KV)
    head = jnp.tile(jnp.arange(KV, dtype=jnp.int32), S)  # r = s·KV + h

    rows_q = jax.lax.broadcasted_iota(jnp.int32, (G * CT, 1), 0) % CT

    def row(args):
        qrow, kcrow, vcrow, trow, o, ln, h = args
        m = jnp.full((G * CT, 1), NEG_INF, jnp.float32)
        l = jnp.zeros((G * CT, 1), jnp.float32)
        acc = jnp.zeros((G * CT, hd), jnp.float32)
        qpos = o + rows_q
        qvalid = rows_q < ln

        def body(carry, i):
            m, l, acc, mk, mv = carry
            b = jnp.maximum(trow[i], 0)          # the kernel's index-map clamp
            tpos = i * BS + jnp.arange(BS, dtype=jnp.int32)
            sel, ku = paged_prefill_merge(kcrow, tpos[:, None], o, ln)
            _, vu = paged_prefill_merge(vcrow, tpos[:, None], o, ln)
            kblk = jnp.where(sel, ku, kp[h, b])
            vblk = jnp.where(sel, vu, vp[h, b])
            mask = qvalid & (tpos[None, :] <= qpos)
            m2, l2, acc2 = flash_prefill_block(
                qrow, kblk, vblk, mask, m, l, acc, scale=scale)
            upd = (i * BS < o + ln) & (ln > 0)   # the kernel's pl.when skip
            wr = upd & (i * BS + BS > o)         # block overlaps the chunk
            mk = mk.at[i].set(jnp.where(wr, kblk, mk[i]))
            mv = mv.at[i].set(jnp.where(wr, vblk, mv[i]))
            return (jnp.where(upd, m2, m), jnp.where(upd, l2, l),
                    jnp.where(upd, acc2, acc), mk, mv), None

        mk0 = jnp.zeros((MB, BS, hd), k_pool.dtype)
        mv0 = jnp.zeros((MB, BS, hd), v_pool.dtype)
        (m, l, acc, mk, mv), _ = jax.lax.scan(
            body, (m, l, acc, mk0, mv0), jnp.arange(MB, dtype=jnp.int32))
        wrote = ((jnp.arange(MB) * BS < o + ln) & (ln > 0)
                 & (jnp.arange(MB) * BS + BS > o))
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype), mk, mv, wrote

    o_r, mk_r, mv_r, wrote_r = jax.lax.map(
        row, (qr, kc, vc, tbl_r, off_r, len_r, head))
    out = (o_r.reshape(S, KV, G, CT, hd).transpose(0, 3, 1, 2, 4)
           .reshape(S, CT, H, hd))
    # scatter the merged chunk blocks back into the pools (the kernel's
    # aliased writeback): only overlapping blocks of live rows write
    bsel = jnp.where(wrote_r & (tbl_r >= 0), tbl_r, NB)  # (R, MB)
    hsel = jnp.broadcast_to(head[:, None], bsel.shape)
    kp2 = kp.at[hsel, bsel].set(mk_r, mode="drop")
    vp2 = vp.at[hsel, bsel].set(mv_r, mode="drop")
    return out, kp2.transpose(1, 2, 0, 3), vp2.transpose(1, 2, 0, 3)


def paged_gather_kv(pool, block_tbl, lens):
    """Dense view of a paged cache: gather ``(S, MB·BS, KV, hd)`` plus the
    per-token position array (`decode_attention_ref` conventions, -1 ⇒
    empty) — the bridge that lets the naive dense oracle cross-check the
    blockwise one."""
    NB, BS, KV, hd = pool.shape
    S, MB = block_tbl.shape
    b = jnp.maximum(jnp.asarray(block_tbl, jnp.int32), 0)
    dense = pool[b].reshape(S, MB * BS, KV, hd)
    t = jnp.arange(MB * BS, dtype=jnp.int32)[None, :]
    pos = jnp.where(t < jnp.asarray(lens, jnp.int32)[:, None], t, -1)
    return dense, pos


# -------------------------------------------------------- decode attention ---


def decode_attention_ref(q, k, v, kv_pos, q_pos, *, window=0):
    """Single-token decode oracle with explicit KV slot positions.
    q: (B,H,hd); k/v: (B,C,KV,hd); kv_pos: (B,C) int32 (-1 ⇒ empty);
    q_pos: (B,) int32. Returns (B,H,hd) in q.dtype."""
    B, H, hd = q.shape
    _, C, KV, _ = k.shape
    group = H // KV
    kh = jnp.repeat(k, group, axis=2).astype(jnp.float32)
    vh = jnp.repeat(v, group, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bchd->bhc", q.astype(jnp.float32), kh) / math.sqrt(hd)
    d = q_pos[:, None] - kv_pos  # (B,C)
    mask = (kv_pos >= 0) & (d >= 0)
    if window > 0:
        mask &= d < window
    s = jnp.where(mask[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhc,bchd->bhd", p, vh).astype(q.dtype)
