"""Calibration of the roofline pipeline (referenced by EXPERIMENTS.md §Roofline):

  * cost_analysis under SPMD reports PER-CHIP flops/bytes;
  * while-loop bodies are counted once (the reason for the analysis lowering);
  * the HLO collective parser's ring formulas on a known program.

These run a 64-device forced host platform in a subprocess (the main test
process keeps the single default CPU device)."""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
    import sys, json
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.hlo_analysis import analyze_collectives
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8, 8), ("data", "model"))
    ns = lambda s: jax.sharding.NamedSharding(mesh, s)
    n = 1024
    x = jax.ShapeDtypeStruct((n, n), jnp.float32)
    w = jax.ShapeDtypeStruct((n, n), jnp.float32)

    out = {}
    # 1. per-chip flops
    c = jax.jit(lambda a, b: a @ b,
                in_shardings=(ns(P("data", None)), ns(P(None, "model")))
                ).lower(x, w).compile()
    out["matmul_flops"] = c.cost_analysis()["flops"]
    out["matmul_expected_per_chip"] = 2 * n**3 / 64

    # 2. while-body counted once
    def scanned(a, b):
        return jax.lax.scan(lambda c_, _: (c_ @ b, None), a, None, length=10)[0]
    c2 = jax.jit(scanned).lower(
        jax.ShapeDtypeStruct((256, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 256), jnp.float32)).compile()
    out["scan_flops"] = c2.cost_analysis()["flops"]
    out["one_body"] = 2 * 256**3

    # 3. collective parse: resharding a model-sharded tensor to replicated
    #    emits an all-gather over the model axis
    def f(a):
        return jax.lax.with_sharding_constraint(a, ns(P("data", None)))
    g = jax.jit(f, in_shardings=ns(P("data", "model")), out_shardings=ns(P("data", None)))
    c3 = g.lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    st = analyze_collectives(c3.as_text(), {"data": 8, "model": 8})
    out["ag_wire"] = st.wire_bytes_per_chip
    out["ag_kinds"] = st.by_kind
    out["ag_axes"] = st.by_axis
    # all-gather over model: out per chip (64/8, 64) f32 = 2048 B? — the
    # resharding gathers the model-sharded dim: out (8, 64) f32 = 2 KiB,
    # wire = out·(n-1)/n
    print(json.dumps(out))
""")


def test_calibration():
    r = subprocess.run([sys.executable, "-c", _SCRIPT], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # per-chip flops exact
    assert abs(out["matmul_flops"] - out["matmul_expected_per_chip"]) < 1e6
    # scan counted once (±epsilon), NOT 10×
    assert out["scan_flops"] < 1.2 * out["one_body"]
    # the reshard emitted an all-gather over the model axis with ring bytes
    assert out["ag_wire"] > 0
    assert "model" in out["ag_axes"]
