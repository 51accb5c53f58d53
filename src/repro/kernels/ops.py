"""Public jit'd wrappers for the Pallas kernels.

The kernels compile natively on a TPU and run in interpret mode on every
other backend; `use_interpret` is the one place that choice is made.
"""

from __future__ import annotations

import jax
import numpy as np

from ..core.functional import next_pow2 as _next_pow2
from .decode_attention import decode_attention as _decode_attention
from .flash_attention import flash_attention_fwd as _flash_attention_fwd
from .paged_decode import paged_decode as _paged_decode
from .paged_prefill import paged_prefill as _paged_prefill
from .qos_admission import qos_round_fused as _qos_round_fused
from .qos_admission import qos_round_scan as _qos_round_scan
from .sema_batch import sema_batch as _sema_batch


def use_interpret() -> bool:
    """Pallas interpret mode off the TPU, native kernels on it."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, window=0, block_q=512, block_k=512):
    return _flash_attention_fwd(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=use_interpret(),
    )


def decode_attention(q, k, v, kv_pos, q_pos, *, window=0, block_k=512):
    return _decode_attention(
        q, k, v, kv_pos, q_pos, window=window, block_k=block_k,
        interpret=use_interpret(),
    )


def paged_decode(q, k_pool, v_pool, block_tbl, lens):
    """Ragged flash-decode over the block-paged KV pool (oracle:
    `ref.paged_decode_ref`, bit-exact in interpret mode)."""
    return _paged_decode(q, k_pool, v_pool, block_tbl, lens,
                         interpret=use_interpret())


def paged_prefill(q, k_chunk, v_chunk, k_pool, v_pool, block_tbl, off, lens):
    """Ragged blockwise flash-prefill of one chunked-prefill round: chunk
    KV written into the slots' freshly-taken pool blocks in the same pass
    (aliased pools), causal-within-chunk + full prior-block attention
    (oracle: `ref.paged_prefill_ref`, bit-exact in interpret mode)."""
    return _paged_prefill(q, k_chunk, v_chunk, k_pool, v_pool, block_tbl,
                          off, lens, interpret=use_interpret())


def sema_batch(ticket, grant, bucket_seq, requests, post_n, salt, *, block_n=512):
    return _sema_batch(
        ticket, grant, bucket_seq, requests, post_n, salt,
        block_n=block_n, interpret=use_interpret(),
    )


def _pad_backlog(tenant_ids, tickets, alive, deadlines, block_n: int):
    """Pad a backlog to the next power of two ≥ block_n, padded rows dead
    (alive=False ⇒ never admitted, expired, or counted).  Steady-state
    serving (backlog ≤ block_n) therefore hits ONE compiled executable for
    every distinct length, and a draining 10k-deep backlog touches
    log₂(N/block_n) shapes instead of one per multiple of block_n —
    compile-cache hits asserted in tests/test_megastep.py."""
    n = len(tenant_ids)
    pad = max(block_n, _next_pow2(n)) - n
    ids = np.pad(np.asarray(tenant_ids, np.int32), (0, pad))
    tks = np.pad(np.asarray(tickets, np.uint32), (0, pad))
    alv = np.pad(np.asarray(alive, bool), (0, pad))
    dls = np.pad(np.asarray(deadlines, np.float32), (0, pad),
                 constant_values=np.inf)
    return ids, tks, alv, dls


def qos_round(state, tenant_ids, tickets, alive, deadlines, now, free_units,
              *, max_units: int, block_n: int = 256):
    """Fused multi-tenant QoS admission round (expire → weighted replenish →
    FCFS admit → reclaim) — `kernels.qos_admission.qos_round_fused` with the
    backlog padded OUTSIDE the jit boundary (see `_pad_backlog`)."""
    n = len(tenant_ids)
    ids, tks, alv, dls = _pad_backlog(tenant_ids, tickets, alive, deadlines,
                                      block_n)
    state2, admitted, expired, leftover = _qos_round_fused(
        state, ids, tks, alv, dls, now, free_units,
        max_units=max_units, block_n=block_n, interpret=use_interpret())
    return state2, admitted[:n], expired[:n], leftover


def qos_round_scan(state, tenant_ids, tickets, alive, deadlines, nows,
                   free_units, released, *, max_units: int,
                   block_n: int = 256):
    """Batch-of-K fused admission rounds (`kernels.qos_admission.
    qos_round_scan`) with the same power-of-two backlog padding — the
    megastep admission spine as a standalone entry point.  Returns
    ``(state', admit_round[:n], expire_round[:n], free')``."""
    n = len(tenant_ids)
    ids, tks, alv, dls = _pad_backlog(tenant_ids, tickets, alive, deadlines,
                                      block_n)
    state2, admit_round, expire_round, free = _qos_round_scan(
        state, ids, tks, alv, dls, nows, free_units, released,
        max_units=max_units, block_n=block_n, interpret=use_interpret())
    return state2, admit_round[:n], expire_round[:n], free
