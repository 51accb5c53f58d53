"""The Granite 4.0-H family (`bench/families/GraniteMoeHybridForCausalLM.py`)
on the CPU at a tiny size: its spec and costs against a hand count at the
published size, its weights' layout against the program's, its reference
against the program's own f32 reference (`repro.models.reference`), and a
tiny cell through the harness, correct, with its int8 control not.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import io
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from benchkit import record  # noqa: E402
from benchkit.driver import run_cell  # noqa: E402
from benchkit.spec import Bench  # noqa: E402

NAME = "granite-4.0-h-micro"
M, A = "mamba", "attention"


def _fam():
    return Bench(ROOT).family(NAME)


def _cfg():
    return json.loads((BENCH / "configs" / f"{NAME}.json").read_text())


# the published layer pattern, cut to two periods of three layers, at
# widths a CPU runs in seconds; every published scalar kept
TINY = dict(name="tiny-granite", hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=96,
            shared_intermediate_size=96, vocab_size=2048, mamba_n_heads=8,
            mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
            num_hidden_layers=6, layer_types=[M, A, M, M, A, M], slots=4)


def _tiny_cfg():
    return dict(_cfg(), **TINY)


# ------------------------------------------------------------ counting ----


def test_spec_and_program_config_at_the_published_size():
    fam, cfg = _fam(), _cfg()
    s = fam.spec(cfg)
    assert s.params() == cfg["params"] == 3_191_396_096
    assert s.period == 10 and s.count(M) == 36 and s.count(A) == 4
    program = fam.program_config(cfg)
    assert program.param_count() + program.d_model == s.params()  # + final norm
    assert [b.kind for b in program.blocks] == [
        {"mamba": "mamba2", "attention": "attn"}[t] for t in cfg["layer_types"]]
    assert (program.rms_eps, program.rope, program.attn_scale, program.embed_mult,
            program.residual_mult, program.logits_div) == (
        1e-5, False, 0.015625, 12.0, 0.22, 8.0)
    for key, value in [("hidden_act", "gelu"), ("mamba_n_groups", 2),
                       ("position_embedding_type", "rope"),
                       ("num_local_experts", 62), ("attention_bias", True)]:
        with pytest.raises(ValueError, match=key):
            fam.program_config(dict(cfg, **{key: value}))


def test_decode_cost_hand_count_granite():
    fam = _fam()
    s = fam.spec(_cfg())
    # one row at position 99 and one at position 0
    flops, nbytes = fam.call_cost(s, record.Call(0.0, 1.0, [99, 0], "decode"))
    d, F = 2048, 8192
    mamba = d * (4096 + 4352 + 64) + 4096 * d + 3 * d * F
    attn = d * (32 + 16) * 64 + 32 * 64 * d + 3 * d * F
    head = 100_352 * d
    per_row = 2 * (36 * mamba + 4 * attn + head) + 5 * 36 * 64 * 64 * 128
    assert flops == 2 * per_row + 4 * 4 * 32 * 64 * (100 + 1)
    state = 36 * (64 * 64 * 128 + 3 * 4352) * 4  # f32, per row
    assert state == 77_377_536
    kv = 4 * 2 * 8 * 64 * 2  # 8,192 B per token
    assert nbytes == (3_191_396_096 * 2 + 2 * 2 * state + kv * (99 + 0 + 2)
                      + 2 * 100_352 * 4)
    assert fam.call_cost(s, record.Call(0.0, 1.0, [], "decode")) == (0.0, 0.0)


def test_leaves_are_laid_out_as_the_programs_params():
    import jax
    import jax.numpy as jnp

    from benchkit.model import check_tree, make_params, tree_paths
    from repro.models.transformer import init_params

    fam = _fam()
    for cfg in (_cfg(), _tiny_cfg()):
        s = fam.spec(cfg)
        program = fam.program_config(cfg)
        check_tree(
            tree_paths(jax.eval_shape(lambda s=s: make_params(fam.leaves(s), 1, jnp.bfloat16))),
            tree_paths(jax.eval_shape(lambda k, p=program: init_params(k, p, jnp.bfloat16),
                                      jax.random.PRNGKey(0))))


def test_the_family_reference_agrees_with_the_programs_reference():
    """Two plain f32 references written apart (the family's from the
    published description, the program's in `repro.models.reference`)
    give the same logits on the same weights: f32 at HIGHEST both, summed
    in other orders, so to about 1e-6 of logits of order 1."""
    import jax
    import jax.numpy as jnp

    from benchkit.model import make_params
    from benchkit.reference import rms
    from repro.models.reference import forward_ref

    fam = _fam()
    cfg = _tiny_cfg()
    s = fam.spec(cfg)
    seed = 2**40 + 17
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(1, s.vocab, 30)]
    served = [int(t) for t in rng.integers(1, s.vocab, 20)]
    xs, _, norm, head, _ = fam.reference_hidden(s, seed, [(prompt, served)])
    ids = prompt + served
    fam_logits = jnp.matmul(rms(xs[0][:len(ids)], norm, s.rms_eps), head,
                            precision=jax.lax.Precision.HIGHEST)
    params = make_params(fam.leaves(s), seed, jnp.bfloat16)
    want = forward_ref(params, fam.program_config(cfg), jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(fam_logits), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------- a tiny cell ----


# the tiny cell's limit on the mean gap: in 4-s windows on three seeds
# (2**33 + 5, 6, 7; 19-24 finished requests, 893-1,128 tokens) the
# program read 1.01e-5 to 1.25e-5 and its int8 control 6.4e-5 to 9.8e-5;
# the logits are small (÷ logits_scaling, the embedding drawn for ×12),
# and so are the gaps
TINY_LIMIT = 3e-5
TINY_SECONDS = 4.0


@pytest.fixture(scope="module")
def granite_root(tmp_path_factory):
    """A checkout's bench with one tiny granite-style cell over 4 slots and
    a 16-deep backlog of short requests; nothing under `bench/benchkit` or
    `bench/families` is written."""
    root = tmp_path_factory.mktemp("granite")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "bench/configs/tiny-granite.json").write_text(json.dumps(_tiny_cfg()))
    mix = json.loads((BENCH / "traffic" / "reason.json").read_text())
    mix["arrivals"].update(depth=16, pool=64)
    mix["prompt"] = {"median": 24, "sigma": 1.2, "min": 4, "max": 300}
    mix["output"] = {"median": 48, "sigma": 0.5, "min": 24, "max": 96}
    (root / "bench/traffic/tiny.json").write_text(json.dumps(mix))
    (root / "bench/cells/tiny-granite.tiny.json").write_text(json.dumps(
        {"kv_ring": 4096, "limits": {"logit_gap_mean": TINY_LIMIT}}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "tiny-granite", "source": "test",
                           "file": "bench/configs/tiny-granite.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "tiny-granite.tiny", "config": "tiny-granite",
                             "traffic": "tiny", "chips": 1, "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if f"{NAME}.reason" in m.get("workloads", ()):
            m["workloads"].append("tiny-granite.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def _tiny_run(root, seed, control=False):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(Bench(root), "tiny-granite.tiny", seed, TINY_SECONDS,
                  control=control, require_tpu=False, compile_cache=False,
                  out=out, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    detail = json.loads(err.getvalue().split("reference: ", 1)[1].splitlines()[0])
    return res, detail


@pytest.mark.parametrize("control", [False, True])
def test_a_tiny_granite_cell(granite_root, control):
    """The tiny cell reads correct, and its int8 control in the program's
    place reads not correct, on the same seed."""
    res, detail = _tiny_run(granite_root, 2**33 + 5, control)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["schedule_mismatch"]["value"] == 0
    assert detail["program_gap_mean"] < TINY_LIMIT
    assert res["correct"] is (not control), res["checks"]
    if control:
        assert detail["control_gap_mean"] > TINY_LIMIT
    assert set(res["metrics"]) == {"out_tok_s", "setup_s"}
