"""Compile-for-chip rehearsals: the served Pallas kernels, a full-width
qwen2-0.5b decode step, `ModelServer`'s decode programs (KV rings donated
and written one slot in place) and granite-4.0-h-micro's prompt-chunk
program, compiled without interpret mode for a described
TPU v5e chip at the widths `chip_smoke.py` serves.  Nothing runs, so these
say nothing about results or times; they catch what only the chip's
compiler refuses (Mosaic layouts, unsupported ops, memory) at no chip
time.  The topology is described inside a fixture (never at import), and
the persistent compilation cache is off while these compile: an entry
written for a described chip cannot be read back without one."""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]


def _served(sharding, arch):
    """(cfg, caches, compiled): `ModelServer`'s decode program for ``arch``
    at the benchmark's widths (bf16, 16 slots, an 8,192-entry ring) as the
    server compiles it, KV rings donated, for the described chip."""
    from repro.configs.registry import get_config
    from repro.launch.serve import decode_program
    from repro.models.transformer import init_caches, init_params, split_caches

    cfg = get_config(arch)
    place = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), t)
    params = place(jax.eval_shape(
        lambda k: init_params(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(
        lambda: init_caches(cfg, 16, 8192, jnp.bfloat16)))
    tok, pos = _shapes(sharding, ((16, 1), jnp.int32), ((16, 1), jnp.int32))
    compiled = decode_program(cfg).lower(
        params, tok, pos, *split_caches(cfg, caches)).compile()
    return cfg, caches, compiled


@pytest.fixture(scope="module")
def served_qwen2(chip):
    return _served(chip, "qwen2-0.5b")


@pytest.fixture(scope="module")
def served_granite(chip):
    return _served(chip, "granite-4.0-h-micro")


_INSTR = re.compile(r"\s+(ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(([^)]*)\)")


def _hlo(text):
    """{computation: [instruction]} of an optimized HLO module's text; an
    instruction is a dict of its name, dims (array outputs only), opcode,
    operand names, called computation and whether it is the ROOT."""
    comps, cur = {}, []
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            head = line.split(" (")[0].split()
            cur = comps.setdefault("ENTRY" if head[0] == "ENTRY" else head[-1].lstrip("%"), [])
            continue
        m = _INSTR.match(line)
        if m:
            calls = re.search(r"calls=%?([\w.\-]+)", line)
            cur.append(dict(root=bool(m.group(1)), name=m.group(2),
                            dims=tuple(int(d) for d in m.group(3).split(",") if d),
                            op=m.group(4), calls=calls and calls.group(1),
                            args=[a.strip().lstrip("%") for a in re.sub(
                                r"/\*.*?\*/", "", m.group(5)).split(",")]))
    return comps


def _qos(sharding):
    from repro.admission.functional_qos import make_qos
    from repro.kernels.qos_admission import qos_round_fused

    # the megastep's admission round: 4 tenants, a 1024-row backlog,
    # 64 slots, the engine's 256-bucket waiting array
    N = 1024
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        make_qos([4.0, 2.0, 1.0, 1.0], table_size=256))
    fn = lambda st, *a: qos_round_fused(st, *a, max_units=64)
    return fn, [state] + _shapes(
        sharding, ((N,), jnp.int32), ((N,), jnp.uint32), ((N,), jnp.bool_),
        ((N,), jnp.float32), ((), jnp.float32), ((), jnp.int32))


def _sema(sharding):
    from repro.kernels.sema_batch import sema_batch

    u = ((), jnp.uint32)
    return sema_batch, _shapes(sharding, u, u, ((256,), jnp.uint32),
                               ((1024,), jnp.bool_), u, u)


# qwen2-0.5b attention geometry over the served pool: 64 slots, a
# 4096-block pool of 16-token blocks, 16-block tables, 32-token chunks
S, H, KV, HD, NB, BS, MB, CT = 64, 14, 2, 64, 4096, 16, 16, 32
POOL = ((NB, BS, KV, HD), jnp.bfloat16)


def _decode(sharding):
    from repro.kernels.paged_decode import paged_decode

    return paged_decode, _shapes(sharding, ((S, H, HD), jnp.bfloat16), POOL,
                                 POOL, ((S, MB), jnp.int32), ((S,), jnp.int32))


def _prefill(sharding):
    from repro.kernels.paged_prefill import paged_prefill

    return paged_prefill, _shapes(
        sharding, ((S, CT, H, HD), jnp.bfloat16),
        ((S, CT, KV, HD), jnp.bfloat16), ((S, CT, KV, HD), jnp.bfloat16),
        POOL, POOL, ((S, MB), jnp.int32), ((S,), jnp.int32),
        ((S,), jnp.int32))


@pytest.mark.parametrize("build", [_qos, _sema, _decode, _prefill],
                         ids=["qos_round_fused", "sema_batch",
                              "paged_decode", "paged_prefill"])
def test_kernel_compiles_for_v5e(chip, build):
    fn, args = build(chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < HBM_BYTES


def test_qwen2_decode_step_fits_v5e(chip):
    """Full-width qwen2-0.5b bf16 decode step at the width `chip_smoke.py`
    serves (4 slots, 384-entry KV ring)."""
    from repro.configs.registry import get_config
    from repro.models.transformer import decode_step, init_caches, init_params

    cfg = get_config("qwen2-0.5b")
    place = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), t)
    params = place(jax.eval_shape(
        lambda k: init_params(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(
        lambda: init_caches(cfg, 4, 384, jnp.bfloat16)))
    tok, pos = _shapes(chip, ((4, 1), jnp.int32), ((4, 1), jnp.int32))
    compiled = jax.jit(lambda p, t, q, c: decode_step(p, cfg, t, q, c)
                       ).lower(params, tok, pos, caches).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0.9e9 < m.argument_size_in_bytes  # ~494M bf16 weights
    assert total < HBM_BYTES, total


def test_qwen2_prefill_chunk_fits_beside_decode_on_v5e(chip):
    """`ModelServer`'s prompt-chunk program at the benchmark's qwen2-0.5b
    widths (16 slots, an 8,192-entry ring, 256-token chunks) updates the
    donated ring in place and needs no more device memory than the decode
    program over the same ring."""
    from repro.configs.registry import get_config
    from repro.models.transformer import (decode_step, init_caches,
                                          init_params, prefill_chunk)

    cfg = get_config("qwen2-0.5b")
    place = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), t)
    params = place(jax.eval_shape(
        lambda k: init_params(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(
        lambda: init_caches(cfg, 16, 8192, jnp.bfloat16)))
    ring = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    tok, pos = _shapes(chip, ((16, 1), jnp.int32), ((16, 1), jnp.int32))
    chunk, row = _shapes(chip, ((256,), jnp.int32), ((), jnp.int32))

    def footprint(compiled):
        m = compiled.memory_analysis()
        return m, (m.argument_size_in_bytes + m.output_size_in_bytes
                   + m.temp_size_in_bytes - m.alias_size_in_bytes)

    _, decode = footprint(jax.jit(
        lambda p, t, q, c: decode_step(p, cfg, t, q, c)
    ).lower(params, tok, pos, caches).compile())
    m, prefill = footprint(jax.jit(
        lambda p, t, q, r, c: prefill_chunk(p, cfg, t, q, c, r),
        donate_argnums=4).lower(params, chunk, chunk, row, caches).compile())
    assert m.alias_size_in_bytes >= ring > 1.6e9  # no second ring
    assert m.temp_size_in_bytes < ring / 2
    assert prefill <= decode, (prefill, decode)


def test_served_qwen2_decode_updates_the_ring_in_place(served_qwen2):
    """`ModelServer`'s qwen2-0.5b decode program takes the donated ring
    and returns it as its output (one 1.62 GB ring, not two), with next to
    no scratch."""
    _, caches, compiled = served_qwen2
    ring = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= ring > 1.6e9
    assert m.temp_size_in_bytes < 0.1e9, m.temp_size_in_bytes


def test_served_qwen2_decode_writes_one_slot_of_the_ring(served_qwen2):
    """No op of that program outputs a whole stacked ring (k, v or pos)
    but a dynamic-update-slice of one slot (alone or as a fusion's root):
    no one-hot select over the ring, no copy of it."""
    _, caches, compiled = served_qwen2
    ring = caches["units"]["b0"]
    shapes = {ring[n].shape for n in ("k", "v", "pos")}
    comps = _hlo(compiled.as_text())
    dims = {i["name"]: i["dims"] for ops in comps.values() for i in ops}

    def one_slot(i):
        if i["op"] == "fusion":
            (root,) = [r for r in comps[i["calls"]] if r["root"]]
            return one_slot(root)
        if i["op"] != "dynamic-update-slice":
            return False
        upd = dims[i["args"][1]]
        return upd[2] == 1 and upd[:2] + upd[3:] == i["dims"][:2] + i["dims"][3:]

    writes = 0
    for comp, ops in comps.items():
        for i in ops:
            if i["dims"] not in shapes or i["op"] in ("parameter", "get-tuple-element",
                                                       "bitcast"):
                continue
            assert one_slot(i), (comp, i["name"], i["op"])
            writes += 1
    assert writes >= 3  # k, v and pos


def test_served_granite_decode_copies_no_state(served_granite):
    """granite-4.0-h-micro's decode program as `ModelServer` compiles it:
    the rings donated and the f32 Mamba-2 state not, so no entry-level copy
    of the state and next to no scratch."""
    _, caches, compiled = served_granite
    state = {x.shape for x in jax.tree.leaves(caches) if x.dtype == jnp.float32}
    assert (4, 16, 64, 64, 128) in state
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 0.1e9, m.temp_size_in_bytes
    copies = [i["name"] for i in _hlo(compiled.as_text())["ENTRY"]
              if i["op"].startswith("copy") and i["dims"] in state]
    assert not copies, copies


def test_granite_hybrid_programs_fit_v5e(chip, served_granite):
    """granite-4.0-h-micro at its published size (bf16 weights, 16 slots,
    an 8,192-entry ring in the 4 attention layers, f32 Mamba-2 state in
    the 36 others): the decode program as `ModelServer` compiles it fits
    one chip beside its new state, and the prompt-chunk program updates the
    donated caches in place."""
    from repro.models.transformer import init_params, prefill_chunk

    cfg, caches, compiled = served_granite
    place = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), t)
    params = place(jax.eval_shape(
        lambda k: init_params(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0)))
    size = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))
    assert size(params) == 2 * 3_191_396_096
    assert 2.3e9 < size(caches) < 2.4e9  # 1.07 GB ring + 1.24 GB state
    chunk, row = _shapes(chip, ((256,), jnp.int32), ((), jnp.int32))

    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < 12e9, total
    m = jax.jit(lambda p, t, q, r, c: prefill_chunk(p, cfg, t, q, c, r),
                donate_argnums=4).lower(params, chunk, chunk, row,
                                        caches).compile().memory_analysis()
    assert m.alias_size_in_bytes >= size(caches)  # no second copy
    assert m.temp_size_in_bytes < 1e9


def test_served_megastep_compiles_for_v5e(chip, monkeypatch):
    """The served megastep — four weighted tenants, the fused admission
    kernel, a 4096-block pool, chunked prefill and the prefix cache — at
    `chip_smoke.py`'s widths (64 slots, a 1024-row backlog).  The engine
    prepares its launch arguments on the CPU; their shapes are then
    compiled for the described chip with the kernel native."""
    import numpy as np

    import repro.kernels.ops as ops
    from repro.serving import engine_state
    from repro.serving.scheduler import ContinuousBatchingEngine, Request

    jitted = engine_state.megastep_jit
    seen = []

    class Launched(Exception):
        pass

    def capture(*args, **static):
        seen.append(jitted.lower(*jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            args), **static).compile())
        raise Launched

    monkeypatch.setattr(engine_state, "megastep_jit", capture)
    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    eng = ContinuousBatchingEngine(
        None, None, 64, tenants={"a": 4.0, "b": 2.0, "c": 1.0, "d": 1.0},
        use_kernel=True, kv_pool=(4096, 16, 16), prompt_cap=128,
        chunked_prefill=(32, 512), prefix_cache=1024, watchdog=64)
    eng.megastep_model = engine_state.make_paged_pool_model(
        jax.random.PRNGKey(0), vocab=32000, d=128, num_blocks=4096,
        block_size=16)
    rng = np.random.default_rng(0)
    eng.submit_batch([
        Request(rid=i, prompt=[int(t) for t in rng.integers(1, 32000, 100)],
                max_new_tokens=16, tenant_id="abcd"[i % 4])
        for i in range(1024)])
    with pytest.raises(Launched):
        eng.megastep(32, token_fn=engine_state.make_chunked_prefill_token_fn(
            32), admit_impl=engine_state.fused_round_impl)
    (compiled,) = seen
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < HBM_BYTES
