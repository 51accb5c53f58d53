"""Plain reference of the engine's schedule: which round admits each
request, and how many tokens it holds when the run ends.

It follows the published semantics of the multi-tenant admission round
(weighted stride replenishment from the pool of free slots, FCFS within a
tenant, surplus credit reclaimed) and of continuous batching (a request's
first token comes in its admission round, one more in every later round,
and its slot returns to the pool in the round its last token comes).  It
is written from that description and imports nothing of the program.
"""

from __future__ import annotations

from collections import deque

import numpy as np


def simulate(weights: list[float], n_slots: int, rounds: int,
             submits: list[tuple[int, int, int, int]]):
    """``submits``: (rid, tenant index, max_new, round) — the request
    enters its tenant's queue before round ``round``, in list order.
    Returns {rid: (admit round or -1, tokens after ``rounds`` rounds)}."""
    S, U = len(weights), n_slots
    w = np.asarray(weights, np.float32)
    grant = np.zeros(S, np.int64)
    consumed = np.zeros(S, np.int64)
    vpass = np.zeros(S, np.float32)
    queues = [deque() for _ in range(S)]
    free = n_slots
    frees_at: dict[int, int] = {}  # round → slots returned during it
    max_new = {}
    admit = {}
    by_round: dict[int, list] = {}
    for rid, t, m, r in submits:
        by_round.setdefault(r, []).append((rid, t))
        max_new[rid] = m
    for r in range(rounds):
        for rid, t in by_round.get(r, ()):
            queues[t].append(rid)
        depth = np.asarray([len(q) for q in queues], np.int64)
        if depth.sum():
            avail = grant - consumed
            unmet = np.clip(depth - avail, 0, U)
            cross = []
            for s in range(S):
                for k in range(int(unmet[s])):
                    step = np.float32(0.0) if k == 0 else np.float32(k) / w[s]
                    cross.append((float(vpass[s] + step), s, k))
            take = min(max(free, 0), U, len(cross))
            alloc = np.zeros(S, np.int64)
            for _, s, _ in sorted(cross)[:take]:
                alloc[s] += 1
            grant += alloc
            vpass = vpass + np.where(alloc > 0, alloc.astype(np.float32) / w,
                                     np.float32(0.0)).astype(np.float32)
            leftover = free - int(alloc.sum())
            avail = grant - consumed
            took = np.minimum(np.maximum(avail, 0), depth)
            for s in range(S):
                for _ in range(int(took[s])):
                    rid = queues[s].popleft()
                    admit[rid] = r
                    last = r + max_new[rid] - 1
                    frees_at[last] = frees_at.get(last, 0) + 1
            consumed += took
            surplus = np.maximum(grant - consumed - (depth - took), 0)
            consumed += surplus
            free = leftover + int(surplus.sum())
        free += frees_at.pop(r, 0)
    out = {}
    for rid, m in max_new.items():
        a = admit.get(rid, -1)
        out[rid] = (a, 0 if a < 0 else min(m, rounds - a))
    return out
