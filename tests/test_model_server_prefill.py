"""`ModelServer`'s chunked prompt prefill against its per-token replay.

A stack of full-context attention blocks prefills a prompt in calls of
`ModelServer.chunk` tokens; every other stack replays it through one
decode call per token (`test_e2e_train.py` covers that path).  A chunked
prefill must leave the server as the replay leaves it: the same cursor,
call count and positions in every row, the same K/V for the row, the
same prompt logits, and another row's decoding untouched."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.launch.serve import ModelServer
from repro.serving.scheduler import Request

RING = 600  # ≥ 2 × chunk + 3 prompt tokens and the neighbour's entries
T = 256  # the chunk a 600-entry ring gets


def _server(chunked: bool) -> ModelServer:
    srv = ModelServer(get_smoke_config("qwen2-0.5b"), 2, max_len=RING)
    assert srv.chunk == T
    if not chunked:
        srv.chunk = 0  # the per-token replay of the same stack
    return srv


def _req(rid, slot, prompt):
    r = Request(rid=rid, prompt=list(prompt), max_new_tokens=1 << 20)
    r.slot = slot
    return r


def _decode(srv, reqs, tok=7):
    """One decode step of ``reqs``; each then holds one more token."""
    logits = srv.step_fn(reqs)
    for r in reqs:
        r.out_tokens.append(tok)
    return logits


def _ring(srv):
    c = srv.caches["units"]["b0"]
    return {n: np.asarray(c[n]) for n in ("k", "v", "pos", "ptr")}


@pytest.mark.parametrize("n", [1, T - 1, T, T + 1, 2 * T + 3])
def test_chunked_prefill_leaves_the_ring_as_replay_does(n):
    prompt = np.random.default_rng(n).integers(1, 512, n)
    runs = {}
    for chunked in (True, False, None):  # None: the neighbour alone
        srv = _server(bool(chunked))
        nb = _req(0, 1, [5, 6, 7, 8, 9])
        srv.prefill_request(nb)
        _decode(srv, [nb])
        _decode(srv, [nb])  # the neighbour is mid-decode
        row = _req(1, 0, prompt)
        if chunked is not None:
            srv.prefill_request(row)
        runs[chunked] = (srv, row, _ring(srv),
                         np.asarray(_decode(srv, [nb]))[0])
    (a, ra, ring_a, nb_a), (b, rb, ring_b, nb_b), (_, _, _, nb_alone) = (
        runs[True], runs[False], runs[None])
    assert a._calls == b._calls == 5 + 2 + n
    np.testing.assert_array_equal(ring_a["ptr"], ring_b["ptr"])
    np.testing.assert_array_equal(ring_a["pos"], ring_b["pos"])
    live = ring_a["pos"][:, 0] >= 0  # the prefilled row's entries
    assert live.sum() == n * ring_a["pos"].shape[0]
    for name in ("k", "v"):
        np.testing.assert_allclose(ring_a[name][:, 0][live],
                                   ring_b[name][:, 0][live],
                                   rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ra._last_logits, rb._last_logits,
                               rtol=1e-4, atol=1e-4)
    # the neighbour's next decode reads as if no prompt had come between
    np.testing.assert_allclose(nb_a, nb_b, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(nb_a, nb_alone, rtol=1e-4, atol=1e-4)
    # and the prefilled row decodes on from its prompt alike
    np.testing.assert_allclose(np.asarray(_decode(a, [ra])),
                               np.asarray(_decode(b, [rb])),
                               rtol=1e-4, atol=1e-4)


def test_no_program_compiles_after_the_first_prefill():
    """One compiled chunk program serves every prompt length up to the
    ring, in either row (a compile inside a benchmark's window is a
    fault)."""
    srv = _server(True)
    srv.prefill_request(_req(0, 0, [1, 2]))
    compiles = []

    def on_event(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for n in range(1, RING + 1):
            srv.prefill_request(_req(n, n % 2, [1 + i % 9 for i in range(n)]))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []
    assert srv._calls == 2 + RING * (RING + 1) // 2
