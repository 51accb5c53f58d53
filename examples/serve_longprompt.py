"""Continuous chunked-prefill example: long prompts through the scanned
megastep with incremental block allocation.

Prompts here are 4–8× longer than the one-shot in-graph prefill previously
handled (PR 4 truncated at the default ``prompt_cap=32`` because the whole
prompt had to land in one admission-round scatter AND its worst-case block
demand had to be free up front).  With ``chunked_prefill=(chunk, budget)``:

  * admission gates on FIRST-CHUNK demand only (behind the no-deadlock
    reserved headroom + the pipelined commitment watermark), so a 256-token
    prompt is admitted the moment 1–2 blocks fit — not when 40 do;
  * every scanned engine round co-schedules prompt chunks with decode,
    Sarathi-style, under the per-round prefill token budget — long prompts
    stream through ``megastep(K)`` with ZERO extra host syncs;
  * blocks are taken from the TWA block semaphore exactly at block-boundary
    crossings; on pool exhaustion the slot PARKS on the semaphore's waiting
    array and resumes FCFS when releases poke its bucket (the stall/park
    policy documented in serving/engine_state.py);
  * `telemetry()` shows the incremental lifecycle: pool_utilization tracks
    WRITTEN blocks (vs the up-front mode's reserved-but-unwritten tails),
    kv_block_stalls / parked_slots count the waiting-array parks, and
    prefill_chunks counts the chunk writes.

Run:  PYTHONPATH=src python examples/serve_longprompt.py

Pool utilization above the worst-case up-front mode's at equal HBM is
held by tests/test_chunked_prefill.py.  The Pallas kernel for real models
(ragged blockwise flash-prefill, causal chunk attention + in-pass pool
writeback) is `kernels/paged_prefill` — oracle-bit-exact, see
tests/test_paged_prefill.py.
"""

import numpy as np


def main(K: int = 24) -> None:
    import jax

    from repro.serving.engine_state import (
        make_chunked_prefill_token_fn,
        make_paged_pool_model,
    )
    from repro.serving.scheduler import ContinuousBatchingEngine, Request

    NB, BS, MB = 128, 8, 40          # 1024 pooled tokens
    CHUNK, BUDGET = 32, 96
    vocab = 50
    eng = ContinuousBatchingEngine(
        lambda a: None, lambda r: None, n_slots=8,
        tenants={"gold": 2.0, "bronze": 1.0},
        kv_pool=(NB, BS, MB), prompt_cap=256,
        chunked_prefill=(CHUNK, BUDGET))
    eng.megastep_model = make_paged_pool_model(
        jax.random.PRNGKey(0), vocab=vocab, d=16, num_blocks=NB,
        block_size=BS)
    tok_fn = make_chunked_prefill_token_fn(CHUNK)

    rng = np.random.default_rng(0)
    reqs, rid = [], 0
    for _ in range(12):
        for t in ("gold", "bronze"):
            plen = int(rng.integers(128, 257))   # 4–8× the old 32-cap table
            reqs.append(Request(
                rid=rid, prompt=list(rng.integers(1, vocab, plen)),
                max_new_tokens=int(rng.integers(8, 24)), tenant_id=t))
            rid += 1
    eng.submit_batch(reqs)

    peak_util, peak_parked = 0.0, 0
    while eng.stats.finished < len(reqs):
        eng.megastep(K, token_fn=tok_fn)
        tel = eng.telemetry()
        peak_util = max(peak_util, tel["pool_utilization"])
        peak_parked = max(peak_parked, tel["parked_slots"])
    tel = eng.telemetry()
    toks = sum(len(r.out_tokens) for r in reqs)
    ptoks = sum(len(r.prompt) for r in reqs)
    print(f"[chunked] served {eng.stats.finished} requests "
          f"({ptoks} prompt + {toks} decode tokens) in "
          f"{eng.stats.host_syncs} host syncs / {eng.stats.steps} rounds")
    print(f"[chunked] prompts up to {max(len(r.prompt) for r in reqs)} tok "
          f"streamed through megastep in {eng.stats.prefill_chunks} chunks "
          f"(≤{CHUNK} tok each, ≤{BUDGET}/round)")
    print(f"[chunked] peak pool utilization {peak_util:.0%} of {NB} blocks; "
          f"{eng.stats.kv_block_stalls} block-stall slot-rounds "
          f"(peak {peak_parked} parked) — resumed FCFS off the waiting "
          f"array; now free={tel['kv_blocks_free']} "
          f"parked={tel['parked_slots']}")
    assert tel["kv_blocks_free"] == NB and tel["parked_slots"] == 0
    assert all(len(r.out_tokens) == r.max_new_tokens for r in reqs)
    print("[example] continuous chunked prefill + incremental block "
          "allocation OK")


if __name__ == "__main__":
    main()
