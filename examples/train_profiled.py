"""End-to-end driver: train a ~100M-param model for a few hundred steps
with checkpointing + per-worker profiling, then run the streaming
aggregation over the collected profiles and print the analysis summary.

This is the paper's full workflow at container scale: measurement
(sparse per-worker profiles) -> post-mortem streaming aggregation ->
PMS/CMS databases a browser would read.

    PYTHONPATH=src python examples/train_profiled.py [--steps 300]
"""
import argparse
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.checkpoint import CheckpointManager
from repro.configs.base import ModelConfig
from repro.core.aggregate import AggregationConfig, StreamingAggregator
from repro.core.cms import CMSReader
from repro.core.pms import PMSReader
from repro.data import TokenPipeline
from repro.models import params as PD
from repro.models.api import build_model
from repro.profiling import Profiler
from repro.train.loop import Trainer, TrainerConfig, make_train_step
from repro.train.optimizer import AdamWConfig

# ~100M params: 12L x 512d x 8H, 32k vocab
CFG_100M = ModelConfig(
    name="repro-100m", family="dense", n_layers=12, d_model=768,
    n_heads=12, n_kv_heads=4, d_ff=2048, vocab_size=32000,
    dtype="float32", remat=False, q_chunk=64, kv_chunk=64)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--out", default="runs/train_profiled")
    # NOTE: the full 300-step default is sized for real hardware; on this
    # CPU container use e.g. --steps 60 --batch 4 --seq 64 (validated).
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    model = build_model(CFG_100M)
    n = PD.count(model.param_defs())
    print(f"model: {n/1e6:.1f}M params")
    pipe = TokenPipeline(CFG_100M.vocab_size, seq_len=args.seq, global_batch=args.batch)
    ckpt = CheckpointManager(os.path.join(args.out, "ckpt"), keep=2)
    # two simulated workers: a host-metric worker and a device-stream worker
    profs = [Profiler({"rank": 0, "stream": 0, "kind": "host"}),
             Profiler({"rank": 0, "stream": 1, "kind": "device"})]
    tr = Trainer(model, AdamWConfig(lr=3e-4, warmup_steps=20),
                 TrainerConfig(steps=args.steps, ckpt_every=100),
                 pipe, ckpt=ckpt, profiler=profs[0])
    params, opt = tr.init_state()

    compiled = jax.jit(make_train_step(model, AdamWConfig())).lower(
        params, opt, {"tokens": jnp.asarray(pipe.batch_at(0))}).compile()
    ca = compiled.cost_analysis()
    profs[1].attribute_compiled(
        compiled.as_text(), measured={"flops": ca.get("flops", 0.0)},
        struct_dir=os.path.join(args.out, "structs"))

    params, opt = tr.run(params, opt, steps=args.steps)
    print(f"loss: {tr.history[0]['loss']:.3f} -> {tr.history[-1]['loss']:.3f} "
          f"over {args.steps} steps")
    assert tr.history[-1]["loss"] < tr.history[0]["loss"]

    paths = []
    for i, p in enumerate(profs):
        path = os.path.join(args.out, f"worker{i}.rprf")
        p.finish(path)
        paths.append(path)
    res = StreamingAggregator(os.path.join(args.out, "db"),
                              AggregationConfig(n_threads=2)).run(paths)
    print(f"analysis: {res.n_contexts} unified contexts, "
          f"{res.n_values} values")
    print(f"sizes: {res.sizes}")
    with PMSReader(res.pms_path) as r, CMSReader(res.cms_path) as c:
        reg = {m["name"]: m["mid"] for m in r.meta["registry"]}
        # top-5 device contexts by HBM bytes across profiles (CMS stripe)
        stats = r.stats
        mask = stats["mid"] == reg.get("dev.bytes_hbm", -1)
        order = stats["sum"][mask].argsort()[::-1][:5]
        ctxs = stats["ctx"][mask][order]
        print("top device contexts by bytes:")
        for ctx in ctxs:
            print(f"  {r.tree.full_path(int(ctx))[:90]}")
    print("train_profiled OK")


if __name__ == "__main__":
    main()
