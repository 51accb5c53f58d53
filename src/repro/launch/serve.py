"""Serving driver: continuous batching with TWA-semaphore FCFS admission
over a real model — the paper's technique as the first-class scheduler of
an inference engine.  The CLI serves the reduced (smoke) config of an
architecture; `ModelServer` serves whatever config and dtype its caller
gives it (``chip_smoke.py`` serves full-width qwen2-0.5b in bf16).

    python -m repro.launch.serve --arch qwen2-0.5b --requests 24 --slots 4
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..configs.registry import get_smoke_config
from ..models.transformer import (
    decode_step,
    init_caches,
    init_params,
    join_caches,
    keep_cache_rows,
    prefill_chunk,
    reset_cache_row,
    split_caches,
)
from ..obs.trace import count, span
from ..serving.scheduler import ContinuousBatchingEngine, Request
from .compile_cache import setup_compile_cache

PREFILL_CHUNK = 256  # prompt tokens per prefill call (at most max_len)


def decode_program(cfg: ModelConfig):
    """`ModelServer`'s jitted decode call: (params, tokens (B,1), positions
    (B,1), rings, states) → (logits, caches), where (rings, states) is
    `split_caches` of the caches.  The rings are donated, so each call
    writes its one slot in place; the recurrent states are not (a layer
    scan's stacked output cannot take the buffer it reads, so a donated
    state would be copied whole), and rows at position -1 keep theirs."""

    def _decode_rows(params, tokens, positions, rings, states):
        caches = join_caches(cfg, rings, states)
        logits, new = decode_step(params, cfg, tokens, positions, caches)
        return logits, keep_cache_rows(cfg, new, caches, positions[:, 0] >= 0)

    return jax.jit(_decode_rows, donate_argnums=3)


class ModelServer:
    """Slot-synchronous batched decode of ``cfg`` with weights and caches
    in ``dtype`` (random weights from ``seed``).

    Every decode call advances the KV ring cursor of every row by one entry
    (`models.cache.make_kv_cache`), so rows that take no part in a call
    write an empty entry (position -1) and keep their recurrent states, a
    new request starts from a reset row, and a request's entries stay in
    the ring for ``max_len`` calls: a call that would overwrite a live
    request's oldest entry raises instead.  A prompt of n tokens counts as
    n calls (`prefill_request`).  Each call takes the KV rings of
    ``caches`` donated (the row reset and the prompt chunks take them all):
    read ``caches`` again after a call, never through a reference kept
    from before it."""

    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int = 128,
                 seed: int = 0, dtype=jnp.float32):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.params = init_params(jax.random.PRNGKey(seed), cfg, dtype)
        self.caches = init_caches(cfg, n_slots, max_len, dtype)
        self.tokens = np.zeros((n_slots, 1), np.int32)
        self.positions = np.zeros((n_slots, 1), np.int32)
        self.row_pos = np.zeros((n_slots,), np.int32)
        self._calls = 0  # decode calls so far == every cache's ring cursor
        self._born = np.zeros((n_slots,), np.int64)  # call at row's prefill
        self._decode = decode_program(cfg)
        self._reset_row = jax.jit(lambda c, row: reset_cache_row(cfg, c, row),
                                  donate_argnums=0)  # in place
        # chunked prefill where every block is full-context attention or
        # Mamba-2 (whose state a chunk takes from the row and writes back);
        # a window, MoE capacity or another recurrent block makes a chunk
        # another computation, so those stacks replay the prompt token by
        # token
        self.chunk = (min(PREFILL_CHUNK, max_len) if all(
            (b.kind == "attn" and b.window == 0) or b.kind == "mamba2"
            for b in cfg.blocks) else 0)
        self._chunk_call = jax.jit(self._prefill_chunk, donate_argnums=4)

    def _prefill_chunk(self, params, tokens, positions, row, caches):
        return prefill_chunk(params, self.cfg, tokens, positions, caches, row)

    def _guard(self, rows, calls):
        """Raise if ``calls`` more calls would overwrite an entry of ``rows``."""
        if (self._calls + calls - 1 - self._born[rows] >= self.max_len).any():
            raise RuntimeError(
                f"the {self.max_len}-entry KV ring would overwrite a live "
                "request's entries; raise max_len")

    def _call(self, rows):
        """One decode call in which only ``rows`` write live entries."""
        self._guard(rows, 1)
        pos = np.full_like(self.positions, -1)
        pos[rows] = self.positions[rows]
        # a copy: the CPU backend may alias a numpy argument, and
        # `self.tokens` changes before this asynchronous call has run
        logits, self.caches = self._decode(
            self.params, jnp.asarray(self.tokens.copy()), jnp.asarray(pos),
            *split_caches(self.cfg, self.caches))
        self._calls += 1
        return logits

    def prefill_request(self, req: Request):
        """Row prefill.  Where every block is full-context attention or
        Mamba-2 the prompt runs in calls of `chunk` tokens (the last one
        padded), one compiled program for every length: each call attends
        the chunk's queries over the row's ring entries and its own,
        causally by position, scans the chunk from the row's Mamba-2 state
        and writes the state back, and leaves the ring as that many decode
        calls in which only this row is live would (its entries at the next
        slots, every other row's entries there empty, the cursor and
        `_calls` advanced by the valid tokens); only the last token's
        logits are formed.
        Other stacks replay the prompt through one decode call per token
        (row-isolated caches make that exact).  Phase spans (under a
        profiler session): ``serve.prefill`` around ``serve.reset``,
        ``serve.replay`` (the host's side of the prompt's calls; a
        ``prefill_chunk`` count per chunk call) and ``serve.prompt_logits``
        (the wait for them and the copy of the last token's logits)."""
        slot = req.slot
        n = len(req.prompt)
        with span("serve.prefill", n):
            with span("serve.reset"):
                self.caches = self._reset_row(self.caches, slot)
            self._born[slot] = self._calls
            with span("serve.replay", n):
                fill = self._prefill_chunks if self.chunk else self._replay
                logits = fill(slot, req.prompt)
            self.row_pos[slot] = n
            with span("serve.prompt_logits"):
                count("d2h")
                req._last_logits = np.asarray(logits)

    def _replay(self, slot, prompt):
        """One decode call per prompt token; the row's last logits."""
        for i, tok in enumerate(prompt):
            self.tokens[slot, 0] = tok
            self.positions[slot, 0] = i
            logits = self._call([slot])
        return logits[slot]

    def _prefill_chunks(self, slot, prompt):
        """The prompt's chunk calls; the last one's logits."""
        T = self.chunk
        at = np.arange(T, dtype=np.int32)
        for c in range(0, len(prompt), T):
            m = min(T, len(prompt) - c)
            self._guard([slot], m)
            tokens = np.zeros(T, np.int32)
            tokens[:m] = prompt[c:c + m]
            count("prefill_chunk")
            logits, self.caches = self._chunk_call(
                self.params, tokens, np.where(at < m, c + at, -1),
                np.int32(slot), self.caches)
            self._calls += m
        return logits

    def step_fn(self, active_reqs):
        """Next-token logits of each request: a request's first token comes
        from its prompt's logits, every later one from a decode call that
        feeds its last token.  Phase spans (under a profiler session):
        ``serve.decode`` (``n``: rows decoded) around ``serve.feed`` (the
        inputs, their copies to the device and the dispatch),
        ``serve.wait`` (until the logits are ready) and ``serve.fetch``
        (their copy to the host and the gather; ``n`` as above)."""
        decoding = [r for r in active_reqs if r.out_tokens]
        n = len(decoding)
        with span("serve.decode", n):
            if decoding:
                with span("serve.feed"):
                    for r in decoding:
                        self.tokens[r.slot, 0] = r.out_tokens[-1]
                        self.positions[r.slot, 0] = self.row_pos[r.slot]
                        self.row_pos[r.slot] += 1
                    logits = self._call([r.slot for r in decoding])
                with span("serve.wait"):
                    logits.block_until_ready()
            with span("serve.fetch", n):
                if decoding:
                    count("d2h")
                    logits = np.asarray(logits)
                return np.stack([logits[r.slot] if r.out_tokens
                                 else r._last_logits for r in active_reqs])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    setup_compile_cache()
    server = ModelServer(get_smoke_config(args.arch), args.slots)
    engine = ContinuousBatchingEngine(
        server.step_fn, server.prefill_request, args.slots)

    rng = np.random.default_rng(args.seed)
    reqs = [
        Request(rid=i, prompt=list(rng.integers(1, server.cfg.vocab, args.prompt_len)),
                max_new_tokens=args.max_new)
        for i in range(args.requests)
    ]
    engine.submit_batch(reqs)
    print(f"[serve] {args.requests} requests, {args.slots} slots, "
          f"queue_depth={engine.telemetry()['queue_depth']}")

    t0 = time.time()
    steps = 0
    sample = lambda lg: lg.argmax(-1)
    while engine.stats.finished < args.requests and steps < 10_000:
        engine.step(sample)
        steps += 1
    dt = time.time() - t0
    tel = engine.telemetry()
    tok = sum(len(r.out_tokens) for r in reqs)
    order_ok = all(
        reqs[i].admit_t <= reqs[j].admit_t + 1e-6
        for i in range(len(reqs)) for j in range(i + 1, len(reqs))
    )
    print(f"[serve] finished={engine.stats.finished} steps={steps} "
          f"tokens={tok} ({tok / dt:.1f} tok/s) fcfs={order_ok}")
    print(f"[serve] TWA scheduler: re-examined={tel['stats']['backlog_scans']} "
          f"skipped={tel['stats']['backlog_skipped']} "
          f"(skip ratio {tel['stats']['backlog_skipped'] / max(1, tel['stats']['backlog_skipped'] + tel['stats']['backlog_scans']):.2f})")
    return engine


if __name__ == "__main__":
    main()
