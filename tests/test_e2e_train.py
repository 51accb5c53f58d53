"""End-to-end driver tests: train loop through the full substrate stack
(pipeline → step → checkpoint → resume) and the serving driver on a real
reduced model."""

from __future__ import annotations

import numpy as np
import pytest

from repro.launch.serve import main as serve_main
from repro.launch.train import main as train_main


def test_train_loop_learns_and_resumes(tmp_path):
    ck = str(tmp_path / "ck")
    losses = train_main([
        "--arch", "qwen2-0.5b", "--smoke", "--steps", "14", "--batch", "4",
        "--seq", "64", "--ckpt-dir", ck, "--ckpt-every", "7", "--lr", "5e-3",
        "--log-every", "50",
    ])
    assert len(losses) == 14
    assert np.mean(losses[-4:]) < np.mean(losses[:4]), "loss did not improve"
    # resume continues from step 14 (checkpointed at the end) for 4 more
    more = train_main([
        "--arch", "qwen2-0.5b", "--smoke", "--steps", "18", "--batch", "4",
        "--seq", "64", "--ckpt-dir", ck, "--resume", "--log-every", "50",
    ])
    assert len(more) == 4  # only the new steps ran


def test_train_moe_arch_runs():
    losses = train_main([
        "--arch", "granite-moe-3b-a800m", "--smoke", "--steps", "6",
        "--batch", "4", "--seq", "32", "--log-every", "50",
    ])
    assert np.isfinite(losses).all()


def test_serve_driver_fcfs():
    eng = serve_main(["--arch", "qwen2-0.5b", "--requests", "10", "--slots", "3",
                      "--prompt-len", "4", "--max-new", "5"])
    assert eng.stats.finished == 10
    # FCFS admission across the run
    reqs = sorted(
        [r for slot_r in [eng.active.values()] for r in slot_r], key=lambda r: r.rid
    )
    assert eng.telemetry()["queue_depth"] == 0


def test_model_server_replay_matches_prefill():
    """Each request's prompt prefill through `ModelServer` (in chunk calls,
    as for every full-attention stack), and each of its decode calls,
    equals the model's own `prefill` of the same tokens, for requests that
    reuse a slot and share calls with other rows."""
    _replay_matches_prefill("qwen2-0.5b")


@pytest.mark.parametrize("arch", ["recurrentgemma-9b", "xlstm-350m"])
def test_model_server_recurrent_rows_isolated(arch):
    """The same for models with recurrent blocks, whose prompts are
    replayed token by token: a reused slot starts from a zero state, and a
    row's state does not move in the calls that replay another row's
    prompt."""
    _replay_matches_prefill(arch)


def _replay_matches_prefill(arch, tok=7):
    import jax.numpy as jnp

    from repro.configs.registry import get_smoke_config
    from repro.launch.serve import ModelServer
    from repro.models.transformer import init_caches, prefill
    from repro.serving.scheduler import ContinuousBatchingEngine, Request

    cfg = get_smoke_config(arch)
    server = ModelServer(cfg, 2, max_len=64)
    assert bool(server.chunk) == (arch == "qwen2-0.5b")  # else: replay
    got = {}  # rid -> the logits each engine step gave it

    def step_fn(active):
        out = server.step_fn(active)
        for r, lg in zip(active, out):
            got.setdefault(r.rid, []).append(lg)
        return out

    eng = ContinuousBatchingEngine(step_fn, server.prefill_request, 2)
    prompts = np.random.default_rng(0).integers(1, cfg.vocab, (4, 8))
    news = (2, 5, 3, 4)  # unequal: a slot is refilled while the other decodes
    reqs = [Request(rid=i, prompt=[int(t) for t in p], max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, news))]
    eng.submit_batch(reqs)
    while eng.stats.finished < len(reqs):
        # a fixed token, so the reference needs no sampling of its own
        eng.step(lambda lg: np.full(len(lg), tok))
    assert len({r.slot for r in reqs}) == 2
    for r in reqs:
        np.testing.assert_array_equal(got[r.rid][0], r._last_logits)
    for j in range(max(news)):
        rows = [r for r in reqs if r.max_new_tokens > j]
        toks = np.asarray([r.prompt + [tok] * j for r in rows], np.int32)
        want, _ = prefill(server.params, cfg, {"tokens": jnp.asarray(toks)},
                          init_caches(cfg, len(rows), toks.shape[1],
                                      jnp.float32))
        np.testing.assert_allclose(np.stack([got[r.rid][j] for r in rows]),
                                   np.asarray(want), rtol=1e-4, atol=1e-4,
                                   err_msg=f"token {j}")
