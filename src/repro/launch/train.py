"""End-to-end training driver (the (b) deliverable's e2e entry point).

Wires every substrate together on whatever devices exist:

    data: SyntheticLM → DataLoader (TWA bounded buffer, FIFO, deterministic)
    model: any --arch config (reduced by default so CPU runs in minutes)
    step: parallel.steps.make_train_step (accum, remat, FSDP when meshed)
    checkpointing: async sharded writes, TWA writer-slot admission,
        atomic publish, resume
    control plane: Coordinator heartbeats + straggler telemetry + SIGTERM
        emergency checkpoint (preemption-safe)

Usage:
    python -m repro.launch.train --arch qwen2-0.5b --smoke --steps 50
    python -m repro.launch.train --arch deepseek-moe-16b --smoke --steps 20 \
        --resume --ckpt-dir /tmp/ck
"""

from __future__ import annotations

import argparse
import signal
import time

import jax
import numpy as np

from ..checkpoint.manager import CheckpointManager
from ..configs.registry import get_config, get_smoke_config
from ..configs.registry import ShapeSpec
from ..data.pipeline import DataLoader, SyntheticLM
from ..optim.adamw import AdamWConfig
from ..parallel import steps as steps_lib
from ..runtime.coordinator import Coordinator


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    shape = ShapeSpec("cli", args.seq, args.batch, "train")
    sc = steps_lib.default_step_config(cfg, shape, dp=1, param_dtype=jax.numpy.float32)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps)

    print(f"[train] arch={cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
          f"params≈{cfg.param_count() / 1e6:.1f}M accum={sc.accum_steps}")

    coord = Coordinator()
    coord.join(0)

    state = steps_lib.make_train_state(jax.random.PRNGKey(args.seed), cfg, sc)
    start_step = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir)
        if args.resume and ckpt.latest_step() is not None:
            state, start_step = ckpt.restore(state)
            print(f"[train] resumed from step {start_step}")

    # preemption safety: SIGTERM → synchronous emergency checkpoint
    last_state = {"state": state, "step": start_step}
    if ckpt is not None:
        def _on_term(signum, frame):
            print("[train] SIGTERM — emergency checkpoint")
            ckpt.save_sync(last_state["step"], last_state["state"])
            raise SystemExit(143)

        signal.signal(signal.SIGTERM, _on_term)

    source = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq, seed=args.seed)
    loader = DataLoader(source, args.batch, n_workers=2, depth=4,
                        start_step=start_step)
    step_fn = jax.jit(steps_lib.make_train_step(cfg, shape, sc, opt_cfg))

    losses = []
    it = iter(loader)
    for step in range(start_step, args.steps):
        t0 = time.time()
        batch = next(it)
        if cfg.frontend == "vision":
            B = batch["tokens"].shape[0]
            batch["patch_embeds"] = np.zeros((B, cfg.n_patches, cfg.d_model), np.float32)
            batch["labels"] = np.concatenate(
                [np.zeros((B, cfg.n_patches), np.int32), batch["labels"]], axis=1)
        elif cfg.frontend == "audio":
            B = batch["tokens"].shape[0]
            emb = np.zeros((B, args.seq, cfg.d_model), np.float32)
            emb[..., 0] = batch["tokens"]  # token-dependent frames (stub)
            batch = {"frame_embeds": emb, "labels": batch["labels"]}
        state, metrics = step_fn(state, batch)
        last_state["state"], last_state["step"] = state, step + 1
        dt = time.time() - t0
        coord.heartbeat(0, step, dt)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            tel = loader.telemetry()
            print(f"[train] step={step} loss={losses[-1]:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} dt={dt:.2f}s "
                  f"input_ready={tel['items_ready']}")
        if ckpt is not None and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, state)
    if ckpt is not None:
        ckpt.save(args.steps, state, blocking=True)
        ckpt.wait()
    loader.stop()
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    print(f"[train] done. loss {first:.3f} → {last:.3f} "
          f"({'improved' if last < first else 'NOT improved'})")
    return losses


if __name__ == "__main__":
    main()
