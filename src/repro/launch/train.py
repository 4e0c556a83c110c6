"""Training launcher.

Runs a (reduced or full) architecture with the full substrate: sharded
train step, deterministic data pipeline, async checkpoints, straggler
watchdog, and the paper's measurement subsystem writing per-worker sparse
profiles for post-mortem analysis.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --reduced --steps 50 --profile-dir runs/profiles
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs.base import get_arch, reduced
from repro.data import TokenPipeline
from repro.models.api import build_model
from repro.profiling import Profiler
from repro.train.loop import Trainer, TrainerConfig, make_train_step
from repro.train.optimizer import AdamWConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--profile-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    pipe = TokenPipeline(cfg.vocab_size, args.seq, args.batch)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    profiler = Profiler({"rank": 0, "stream": 0, "kind": "host"}) \
        if args.profile_dir else None
    tr = Trainer(model, AdamWConfig(lr=args.lr, warmup_steps=10),
                 TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                               microbatches=args.microbatches,
                               deadline_s=30.0),
                 pipe, ckpt=ckpt, profiler=profiler)
    start = 0
    params = opt = None
    if args.resume and ckpt is not None:
        step, state = ckpt.restore()
        if state is not None:
            start = step
            params, opt = state["params"], state["opt"]
            params = jax.tree_util.tree_map(jnp.asarray, params)
            opt = jax.tree_util.tree_map(jnp.asarray, opt)
            print(f"resumed from step {step}")
    if params is None:
        params, opt = tr.init_state()

    if profiler is not None:
        compiled = jax.jit(make_train_step(model, AdamWConfig())).lower(
            params, opt, {"tokens": jnp.asarray(pipe.batch_at(start))}).compile()
        ca = compiled.cost_analysis()
        profiler.attribute_compiled(compiled.as_text(),
                                    measured={"flops": ca.get("flops", 0.0)},
                                    struct_dir=os.path.join(args.profile_dir,
                                                            "structs"))

    params, opt = tr.run(params, opt, start_step=start, steps=args.steps)
    print(json.dumps(tr.history[-3:], indent=2))
    if profiler is not None:
        os.makedirs(args.profile_dir, exist_ok=True)
        profiler.finish(os.path.join(args.profile_dir, "worker0.rprf"))
        print(f"profile written to {args.profile_dir}/worker0.rprf")


if __name__ == "__main__":
    main()
