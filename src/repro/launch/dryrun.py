import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell on the
production mesh built from 512 host placeholder devices, and extract the
roofline inputs.

Two lowerings per cell (see EXPERIMENTS.md §Method):

  production — scan-over-layers, microbatch accumulation, blockwise
               attention: the artifact that would ship.  Source of
               memory_analysis() (true per-device allocation).
  analysis   — identical math with every static-trip-count loop unrolled
               (units scan, KV-block scan, CE-chunk scan, accumulation
               collapsed to A=1).  Source of cost_analysis() FLOPs/bytes and
               the HLO collective parse — XLA counts a while-loop body ONCE,
               so the production artifact *undercounts* by the trip counts;
               the analysis artifact does not.  Residual undercount: the
               xLSTM time-step scans (nonlinear recurrences cannot be
               unrolled at S=4k/32k); corrected analytically via
               scan_flop_correction() and flagged in the output.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --out experiments/dryrun
"""

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ..configs.registry import SHAPES, get_config, shapes_for  # noqa: E402
from ..optim.adamw import AdamWConfig  # noqa: E402
from ..parallel import steps as steps_lib  # noqa: E402
from ..parallel.sharding import batch_pspecs, cache_pspecs, param_pspecs  # noqa: E402
from .hlo_analysis import analyze_collectives, analytic_hbm_bytes, roofline_terms  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402


# ------------------------------------------------------- analytic helpers ---


def scan_flop_correction(cfg, shape) -> float:
    """(trips−1) × body-FLOPs for the unavoidable nonlinear time-step scans
    (mLSTM / sLSTM).  Train counts fwd+recompute+bwd ≈ 4× fwd body."""
    kinds = [b.kind for b in cfg.blocks]
    n_ml = kinds.count("mlstm")
    n_sl = kinds.count("slstm")
    if n_ml + n_sl == 0 or shape.kind == "decode":
        return 0.0
    B, S = shape.global_batch, shape.seq_len
    d_in = 2 * cfg.d_model
    H = cfg.xlstm_heads
    hd = d_in // H
    ml_body = B * (5 * H * hd * hd + 6 * H * hd)  # C/n update + qC readout
    hd_s = cfg.d_model // H
    sl_body = B * (2 * H * hd_s * 4 * hd_s + 30 * H * hd_s)  # recurrent mm + gates
    per_step = n_ml * ml_body + n_sl * sl_body
    mult = 4.0 if shape.kind == "train" else 1.0
    return (S - 1) * per_step * mult


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE); D = tokens/step."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    if shape.kind == "train":
        return 6.0 * n * tokens
    return 2.0 * n * tokens  # inference: fwd only


def attn_model_flops(cfg, shape) -> float:
    """Attention-score/PV FLOPs not counted by 6·N·D (reported separately so
    the useful-compute ratio can be read against the right denominator).
    Causal/windowed structure credited (factor ½ or window/S)."""
    n_attn = sum(1 for b in cfg.blocks if b.kind in ("attn", "moe"))
    if n_attn == 0:
        return 0.0
    B, S = shape.global_batch, shape.seq_len
    H, hd = cfg.n_heads, cfg.hd
    total = 0.0
    for b in cfg.blocks:
        if b.kind not in ("attn", "moe"):
            continue
        if shape.kind == "decode":
            ctx = min(S, b.window) if b.window else S
            total += 4.0 * B * H * ctx * hd  # one query vs cache
        else:
            frac = min(1.0, b.window / S) if b.window else 0.5  # causal half
            fb = 3.0 if shape.kind == "train" else 1.0  # fwd(+bwd≈2×)
            total += 4.0 * B * H * S * S * frac * hd * fb
    return total


# ----------------------------------------------------------- cell dry-run ---


def build_step(cfg, shape, sc):
    if shape.kind == "train":
        step = steps_lib.make_train_step(cfg, shape, sc, AdamWConfig())
        state = steps_lib.abstract_train_state(cfg, sc)
        specs = steps_lib.train_state_pspecs(state, sc)
        ins = steps_lib.input_specs(cfg, shape)
        args = (state, ins["batch"])
        in_specs = (specs, batch_pspecs(ins["batch"]))
        donate = (0,)
    elif shape.kind == "prefill":
        step = steps_lib.make_prefill_step(cfg, shape, sc)
        params = jax.eval_shape(
            lambda: steps_lib.init_params(jax.random.PRNGKey(0), cfg, sc.param_dtype)
        )
        ins = steps_lib.input_specs(cfg, shape)
        args = (params, ins["batch"], ins["caches"])
        in_specs = (param_pspecs(params, fsdp=sc.fsdp), batch_pspecs(ins["batch"]),
                    cache_pspecs(ins["caches"]))
        donate = (2,)
    else:
        step = steps_lib.make_decode_step(cfg, shape, sc)
        params = jax.eval_shape(
            lambda: steps_lib.init_params(jax.random.PRNGKey(0), cfg, sc.param_dtype)
        )
        ins = steps_lib.input_specs(cfg, shape)
        args = (params, ins["tokens"], ins["positions"], ins["caches"])
        in_specs = (
            param_pspecs(params, fsdp=sc.fsdp),
            batch_pspecs({"t": ins["tokens"]})["t"],
            batch_pspecs({"p": ins["positions"]})["p"],
            cache_pspecs(ins["caches"]),
        )
        donate = (3,)
    return step, args, in_specs, donate


def _analytic_args_bytes(in_specs, args, mesh) -> dict:
    """Exact per-device bytes of every input tree, from ShapeDtypeStructs ×
    sharding divisors.  This is the TPU ground truth for weights/opt/caches —
    the CPU host backend *widens every bf16 buffer to f32* (HLO shows
    wrapped_convert fusions of whole parameter/cache stacks), inflating
    memory_analysis() temps by up to 2×; see EXPERIMENTS.md §Dry-run."""
    axis_size = dict(mesh.shape_tuple)

    def spec_div(spec):
        n = 1
        for entry in spec:
            if entry is None:
                continue
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                n *= axis_size.get(ax, 1)
        return n

    total = 0.0
    flat_a = jax.tree_util.tree_leaves(args)
    flat_s = jax.tree_util.tree_leaves(
        in_specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    for a, s in zip(flat_a, flat_s):
        import numpy as _np

        n = float(_np.prod(a.shape)) if a.shape else 1.0
        total += n * a.dtype.itemsize / spec_div(s)
    return total


def run_cell(arch: str, shape_name: str, mesh_kind: str, mode: str,
             hlo_dir: str | None = None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    multi_pod = mesh_kind == "multipod"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "mode": mode,
        "chips": n_chips, "ok": False,
    }
    t0 = time.time()
    HBM_BUDGET = 14.5e9  # v5e 16 GB minus runtime reserve
    with jax.set_mesh(mesh):
        dp = steps_lib.dp_size()
        sc = steps_lib.default_step_config(cfg, shape, dp, analysis=(mode == "analysis"))
        max_accum = max(1, shape.global_batch // max(dp, 1))
        while True:
            step, args, in_specs, donate = build_step(cfg, shape, sc)
            to_sharding = lambda spec: jax.sharding.NamedSharding(mesh, spec)
            in_shardings = jax.tree.map(
                to_sharding, in_specs,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
            jitted = jax.jit(step, in_shardings=in_shardings, donate_argnums=donate)
            lowered = jitted.lower(*args)
            rec["t_lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            rec["t_compile_s"] = round(time.time() - t1, 1)

            mem = compiled.memory_analysis()
            rec["memory"] = {
                k: getattr(mem, k, None)
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes")
            }
            args_b = rec["memory"]["argument_size_in_bytes"] or 0
            alias_b = rec["memory"]["alias_size_in_bytes"] or 0
            out_b = rec["memory"]["output_size_in_bytes"] or 0
            tmp_b = rec["memory"]["temp_size_in_bytes"] or 0
            rec["memory"]["per_device_total_bytes"] = args_b + tmp_b + max(0, out_b - alias_b)
            rec["step_config"] = {"accum_steps": sc.accum_steps, "remat": sc.remat,
                                  "fsdp": sc.fsdp}
            # TPU-projected steady-state memory: exact sharded input bytes +
            # activation estimate (CPU memory_analysis widens bf16 → f32).
            args_exact = _analytic_args_bytes(in_specs, args, mesh)
            if shape.kind == "train":
                per_chip_tokens = shape.global_batch * shape.seq_len / max(dp, 1)
                act = steps_lib.est_train_act_bytes(
                    cfg, per_chip_tokens / sc.accum_steps,
                    dict(mesh.shape_tuple).get("model", 1))
                if sc.remat == "2level":
                    import math as _m

                    g = _m.isqrt(cfg.num_units) or 1
                    act *= (cfg.num_units // g + g) / max(cfg.num_units, 1)
            else:
                act = 4 * shape.global_batch * max(1, shape.seq_len if shape.kind == "prefill" else 1) \
                    * cfg.d_model * 2 / max(dp, 1)
            rec["memory"]["tpu_projected_bytes"] = args_exact + act
            rec["memory"]["analytic_args_bytes"] = args_exact
            # memory auto-tuner: production train cells double accumulation
            # until the artifact fits the per-chip HBM budget, then fall back
            # to nested (√L) remat (analysis lowerings are never executed
            # and always use A=1).
            if (mode == "production" and shape.kind == "train"
                    and rec["memory"]["per_device_total_bytes"] > HBM_BUDGET):
                if sc.accum_steps < max_accum:
                    sc = sc._replace(accum_steps=sc.accum_steps * 2)
                    rec["retuned"] = True
                    continue
                if sc.remat != "2level":
                    sc = sc._replace(remat="2level")
                    rec["retuned"] = True
                    continue
            break

        ca = compiled.cost_analysis() or {}
        rec["cost"] = {"flops": ca.get("flops", 0.0),
                       "bytes_accessed": ca.get("bytes accessed", 0.0),
                       "transcendentals": ca.get("transcendentals", 0.0)}

    rec["ok"] = True
    rec["t_total_s"] = round(time.time() - t0, 1)
    return rec


def _measure_analysis(cfg, shape, mesh, hlo_path=None, sc_over=None) -> dict:
    """One analysis lowering (all loops unrolled) → flops/bytes/collectives."""
    dp = steps_lib.dp_size()
    sc = steps_lib.default_step_config(cfg, shape, dp, analysis=True, **(sc_over or {}))
    step, args, in_specs, donate = build_step(cfg, shape, sc)
    to_sharding = lambda spec: jax.sharding.NamedSharding(mesh, spec)
    in_shardings = jax.tree.map(to_sharding, in_specs,
                                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    compiled = jax.jit(step, in_shardings=in_shardings,
                       donate_argnums=donate).lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    if hlo_path:
        Path(hlo_path).parent.mkdir(parents=True, exist_ok=True)
        Path(hlo_path).write_text(text)
    axis_sizes = {name: size for name, size in mesh.shape_tuple}
    coll = analyze_collectives(text, axis_sizes)
    return {
        "flops": ca.get("flops", 0.0),
        "bytes_accessed": ca.get("bytes accessed", 0.0),
        "wire": coll.wire_bytes_per_chip,
        "ops": coll.ops,
        "by_kind": coll.by_kind,
        "by_axis": coll.by_axis,
        "top": sorted(coll.details, key=lambda d: -d["wire"])[:12],
    }


def _lin(base: dict, delta: dict, extra_units: float) -> dict:
    """base + extra_units × delta, linearly over all numeric fields/dicts."""
    out = {}
    for k, b in base.items():
        d = delta.get(k, 0)
        if isinstance(b, dict):
            keys = set(b) | set(d if isinstance(d, dict) else {})
            out[k] = {kk: b.get(kk, 0.0) + extra_units * (d.get(kk, 0.0) if isinstance(d, dict) else 0.0)
                      for kk in keys}
        elif isinstance(b, (int, float)):
            out[k] = b + extra_units * d
        else:
            out[k] = b
    return out


def run_analysis(arch: str, shape_name: str, mesh_kind: str,
                 hlo_dir: str | None = None, sc_over: dict | None = None) -> dict:
    """Roofline measurement.  XLA counts a while-loop body once, so every
    static loop is unrolled; for deep stacks (U > 4) compiling the unrolled
    program is infeasible on this host, so we exploit exact per-unit
    linearity: measure U'∈{2,4} fully unrolled and reconstruct
    f(U) = f(4) + (U−4)·(f(4)−f(2))/2 — identical repeated units make
    flops/bytes/collective traffic affine in U (verified by the U=4 direct
    measurements for small archs)."""
    import dataclasses

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    n_chips = mesh.size
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "mode": "analysis",
           "chips": n_chips, "ok": False}
    t0 = time.time()
    with jax.set_mesh(mesh):
        U = cfg.num_units
        hlo = (Path(hlo_dir) / f"{arch}_{shape_name}_{mesh_kind}.hlo") if hlo_dir else None
        if U <= 4:
            meas = _measure_analysis(cfg, shape, mesh, hlo, sc_over)
            rec["reconstruction"] = "direct"
        else:
            m2 = _measure_analysis(dataclasses.replace(cfg, num_units=2), shape, mesh, None, sc_over)
            m4 = _measure_analysis(dataclasses.replace(cfg, num_units=4), shape, mesh, hlo, sc_over)
            delta = {k: ({kk: (m4[k].get(kk, 0.0) - m2[k].get(kk, 0.0)) / 2 for kk in set(m4[k]) | set(m2[k])}
                         if isinstance(m4[k], dict) else
                         ((m4[k] - m2[k]) / 2 if isinstance(m4[k], (int, float)) else m4[k]))
                     for k in m4}
            meas = _lin(m4, delta, U - 4)
            meas["top"] = m4["top"]
            rec["reconstruction"] = {"u_points": [2, 4], "per_unit_flops": delta["flops"]}
        rec["cost"] = {"flops": meas["flops"], "bytes_accessed": meas["bytes_accessed"]}
        rec["collectives"] = {"ops": meas["ops"], "wire_bytes_per_chip": meas["wire"],
                              "by_kind": meas["by_kind"], "by_axis": meas["by_axis"],
                              "top": meas["top"]}
        correction = scan_flop_correction(cfg, shape)
        flops_chip = meas["flops"] + correction / n_chips
        rec["flops_correction_total"] = correction

        class _C:  # tiny adapter for roofline_terms
            by_axis = meas["by_axis"]

        terms = roofline_terms(flops_chip, meas["bytes_accessed"], _C)
        # analytic (TPU-projected) memory term; HLO bytes stay as upper bound
        axes = dict(mesh.shape_tuple)
        mem_model = analytic_hbm_bytes(cfg, shape, axes, accum=1)
        terms["T_mem_hlo_upper"] = terms["T_mem"]
        terms["T_mem"] = mem_model / 819e9
        terms["hbm_model_bytes"] = mem_model
        bound = max(terms["T_comp"], terms["T_mem"], terms["T_coll"])
        terms["bottleneck"] = max(
            ("T_comp", "T_mem", "T_coll"), key=lambda k: terms[k])
        terms["roofline_fraction"] = terms["T_comp"] / bound if bound else 0.0
        mf = model_flops(cfg, shape)
        terms["model_flops_total"] = mf
        terms["hlo_flops_total"] = flops_chip * n_chips
        terms["useful_ratio"] = mf / max(terms["hlo_flops_total"], 1.0)
        terms["attn_model_flops_total"] = attn_model_flops(cfg, shape)
        terms["useful_ratio_with_attn"] = (mf + terms["attn_model_flops_total"]) / max(
            terms["hlo_flops_total"], 1.0)
        rec["roofline"] = terms
    rec["ok"] = True
    rec["t_total_s"] = round(time.time() - t0, 1)
    return rec


# ------------------------------------------------------------------ main ----


def cell_list():
    cells = []
    from ..configs.registry import ARCH_IDS

    for arch in ARCH_IDS:
        for shape in shapes_for(arch):
            cells.append((arch, shape.name))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--mode", default="production", choices=["production", "analysis"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--hlo-dir", default=None, help="dump optimized HLO text here")
    ap.add_argument("--all", action="store_true", help="run every cell as subprocesses")
    ap.add_argument("--modes", default="production,analysis")
    ap.add_argument("--meshes", default="pod,multipod")
    ap.add_argument("--remat", default=None, help="override remat policy (hillclimb)")
    ap.add_argument("--tag", default=None, help="output-name suffix (hillclimb variants)")
    args = ap.parse_args()
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    if args.all:
        jobs = []
        for arch, shape in cell_list():
            for mesh in args.meshes.split(","):
                for mode in args.modes.split(","):
                    if mode == "analysis" and mesh == "multipod":
                        continue  # roofline table is single-pod (assignment)
                    jobs.append((arch, shape, mesh, mode))
        print(f"[dryrun] {len(jobs)} jobs")
        failures = 0
        for i, (arch, shape, mesh, mode) in enumerate(jobs):
            tag = f"{arch}_{shape}_{mesh}_{mode}"
            out_json = outdir / f"{tag}.json"
            if out_json.exists() and json.loads(out_json.read_text()).get("ok"):
                print(f"[{i + 1}/{len(jobs)}] {tag}: cached")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape, "--mesh", mesh, "--mode", mode, "--out", str(outdir)]
            if args.hlo_dir:
                cmd += ["--hlo-dir", args.hlo_dir]
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True)
            ok = out_json.exists() and json.loads(out_json.read_text()).get("ok")
            print(f"[{i + 1}/{len(jobs)}] {tag}: {'ok' if ok else 'FAIL'} "
                  f"({time.time() - t0:.0f}s)")
            if not ok:
                failures += 1
                (outdir / f"{tag}.err").write_text(r.stdout[-4000:] + "\n" + r.stderr[-8000:])
        print(f"[dryrun] done, {failures} failures")
        sys.exit(1 if failures else 0)

    tag = f"{args.arch}_{args.shape}_{args.mesh}_{args.mode}"
    if args.tag:
        tag += f"_{args.tag}"
    try:
        sc_over = {"remat": args.remat} if args.remat else None
        if args.mode == "analysis":
            rec = run_analysis(args.arch, args.shape, args.mesh, args.hlo_dir, sc_over)
        else:
            rec = run_cell(args.arch, args.shape, args.mesh, args.mode, args.hlo_dir)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "mode": args.mode, "ok": False, "error": traceback.format_exc()}
        (outdir / f"{tag}.json").write_text(json.dumps(rec, indent=2))
        print(rec["error"], file=sys.stderr)
        sys.exit(1)
    (outdir / f"{tag}.json").write_text(json.dumps(rec, indent=2))
    mem = rec.get("memory", {})
    print(f"[dryrun] {tag}: ok mem/device="
          f"{(mem.get('per_device_total_bytes') or 0) / 1e9:.2f} GB raw / "
          f"{(mem.get('tpu_projected_bytes') or 0) / 1e9:.2f} GB projected "
          f"flops={rec['cost']['flops']:.3e} t={rec.get('t_total_s')}s")
    if "roofline" in rec:
        r = rec["roofline"]
        print(f"  T_comp={r['T_comp'] * 1e3:.2f}ms T_mem={r['T_mem'] * 1e3:.2f}ms "
              f"T_coll={r['T_coll'] * 1e3:.2f}ms bottleneck={r['bottleneck']} "
              f"frac={r['roofline_fraction']:.2f} useful={r['useful_ratio']:.2f}")


if __name__ == "__main__":
    main()
