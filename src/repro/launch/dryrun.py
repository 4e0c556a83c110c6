"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: every cell
must ``.lower().compile()`` for the single-pod (16x16 = 256 chip) and
multi-pod (2x16x16 = 512 chip) production meshes, and reports
``memory_analysis()`` (fits?) + ``cost_analysis()`` + collective bytes
(the §Roofline inputs).

Usage::

    python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    python -m repro.launch.dryrun --all --out runs/dryrun
    python -m repro.launch.dryrun --arch yi-6b --shape train_4k \
        --set causal_mode=triangle --microbatches 4   # hillclimb variants
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder devices; jax
# locks the device count on first init, so this precedes every import.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.analysis import roofline
from repro.configs.base import SHAPES, load_all
from repro.launch.mesh import make_production_mesh
from repro.models import params as PD
from repro.models.api import (batch_specs, batch_struct, build_model,
                              cache_struct_and_specs, model_flops,
                              n_active_params, n_params, rules_for)
from repro.sharding.specs import set_rules
from repro.train.loop import make_train_step
from repro.train.optimizer import AdamWConfig

# long-context decode requires sub-quadratic history handling: only the
# SSM/hybrid archs run long_500k (DESIGN.md §Arch-applicability).
LONG_OK = {"zamba2-7b", "xlstm-350m"}


def cell_is_skipped(arch: str, shape_name: str) -> str | None:
    if shape_name == "long_500k" and arch not in LONG_OK:
        return "full-attention arch: 500k dense KV decode is out of family"
    return None


def _named(tree, mesh):
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                overrides: dict | None = None, microbatches: int = 1,
                fsdp: bool | None = None, seq_shard: bool = False,
                donate: bool = True) -> dict:
    archs = load_all()
    cfg = archs[arch]
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    if shape.kind == "train" and microbatches == 1 \
            and shape.global_batch * shape.seq_len >= 1 << 20:
        # default gradient accumulation: bounds per-layer activation
        # residuals (the remat-saved per-layer carries) at ~1/8th; deep
        # stacks (zamba2: 81 layers) save a carry per layer -> go deeper
        microbatches = 16 if cfg.n_layers > 64 else 8
    # largest models (grok-1): f32 AdamW state alone exceeds a pod's HBM
    # (316e9 x 14 B/param = 4.4 TB > 256 x 16 GB) — physics, not sharding.
    # Runnable config: bf16 moments + bf16 grad accumulation (10 B/param)
    # and deeper accumulation.
    moment_dtype = jnp.float32
    accum_dtype = jnp.float32
    if shape.kind == "train" and 14 * n_params(cfg) / n_chips > 8e9:
        moment_dtype = jnp.bfloat16
        accum_dtype = jnp.bfloat16
        microbatches = max(microbatches, 16)
    kind = shape.kind
    rules_kind = "decode_sp" if (kind == "decode" and
                                 shape.global_batch < mesh.shape["data"]) \
        else kind
    rules = rules_for(cfg, mesh, rules_kind, fsdp=fsdp, seq_shard=seq_shard)
    model = build_model(cfg)
    dtype = jnp.dtype(cfg.dtype)

    defs = model.param_defs()
    params_sds = PD.shapedtypes(defs, dtype)
    pspecs = _named(PD.specs(defs, rules), mesh)
    bs_sds = batch_struct(cfg, shape)
    bspecs = _named(batch_specs(cfg, shape, rules), mesh)

    t0 = time.perf_counter()
    with mesh, set_rules(mesh, rules):
        if kind == "train":
            opt_sds = {
                "m": PD.shapedtypes(defs, moment_dtype),
                "v": PD.shapedtypes(defs, moment_dtype),
                "step": jax.ShapeDtypeStruct((), jnp.int32),
            }
            ospecs = {"m": pspecs, "v": pspecs,
                      "step": NamedSharding(mesh, jax.sharding.PartitionSpec())}
            step = make_train_step(model, AdamWConfig(), mesh=mesh,
                                   rules=rules, microbatches=microbatches,
                                   accum_dtype=accum_dtype)
            fn = jax.jit(step, in_shardings=(pspecs, ospecs, bspecs),
                         donate_argnums=(0, 1) if donate else ())
            lowered = fn.lower(params_sds, opt_sds, bs_sds)
        elif kind == "prefill":
            fn = jax.jit(lambda p, b: model.prefill(p, b),
                         in_shardings=(pspecs, bspecs))
            lowered = fn.lower(params_sds, bs_sds)
        else:  # decode
            cache_sds, cache_specs = cache_struct_and_specs(model, cfg, shape, rules)
            cspecs = _named(cache_specs, mesh)
            fn = jax.jit(lambda p, c, b: model.decode_step(p, c, b),
                         in_shardings=(pspecs, cspecs, bspecs),
                         donate_argnums=(1,) if donate else ())
            lowered = fn.lower(params_sds, cache_sds, bs_sds)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    rf = roofline.analyze(compiled, n_chips=n_chips,
                          model_flops=model_flops(cfg, shape))
    ca = compiled.cost_analysis()
    hbm_per_dev = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips, "kind": kind, "rules_kind": rules_kind,
        "n_params": n_params(cfg), "n_active_params": n_active_params(cfg),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_bytes": hbm_per_dev,
            "fits_16GiB": bool(hbm_per_dev < 16 * 2**30),
        },
        "roofline": rf.to_dict(),
        "collectives": rf.coll_by_kind,
        "xla_cost_analysis": {"flops": ca.get("flops", 0.0),
                              "bytes_accessed": ca.get("bytes accessed", 0.0)},
        "timings": {"lower_s": t_lower, "compile_s": t_compile},
        "overrides": overrides or {}, "microbatches": microbatches,
        "moment_dtype": str(jnp.dtype(moment_dtype)),
    }


def _parse_overrides(items):
    out = {}
    for kv in items or []:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("true", "True"):
            v = True
        if v in ("false", "False"):
            v = False
        out[k] = v
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--set", dest="sets", action="append",
                    help="ModelConfig override k=v (hillclimb lever)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--fsdp", choices=["auto", "on", "off"], default="auto")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    fsdp = {"auto": None, "on": True, "off": False}[args.fsdp]
    overrides = _parse_overrides(args.sets)

    if not args.all:
        skip = cell_is_skipped(args.arch, args.shape)
        if skip:
            print(json.dumps({"arch": args.arch, "shape": args.shape,
                              "skipped": skip}))
            return
        res = dryrun_cell(args.arch, args.shape, multi_pod=args.multipod,
                          overrides=overrides, microbatches=args.microbatches,
                          fsdp=fsdp, seq_shard=args.seq_shard)
        print(json.dumps(res, indent=2))
        if args.tag:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, f"{args.tag}.json"), "w") as f:
                json.dump(res, f, indent=2)
        return

    os.makedirs(args.out, exist_ok=True)
    archs = sorted(load_all())
    ok = fail = skipped = 0
    for multi_pod in (False, True):
        for arch in archs:
            for shape_name in SHAPES:
                mesh_tag = "multi" if multi_pod else "single"
                tag = f"{arch}.{shape_name}.{mesh_tag}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    ok += 1
                    continue
                skip = cell_is_skipped(arch, shape_name)
                if skip:
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape_name,
                                   "mesh": mesh_tag, "skipped": skip}, f)
                    skipped += 1
                    continue
                t0 = time.perf_counter()
                try:
                    res = dryrun_cell(arch, shape_name, multi_pod=multi_pod)
                    with open(path, "w") as f:
                        json.dump(res, f, indent=2)
                    ok += 1
                    print(f"OK   {tag:48s} {time.perf_counter()-t0:6.1f}s "
                          f"dom={res['roofline']['dominant']:10s} "
                          f"mem={res['memory']['peak_per_device_bytes']/2**30:6.2f}GiB",
                          flush=True)
                except Exception as e:
                    fail += 1
                    with open(path + ".err", "w") as f:
                        f.write(traceback.format_exc())
                    print(f"FAIL {tag:48s} {type(e).__name__}: {str(e)[:120]}",
                          flush=True)
    print(f"done: ok={ok} fail={fail} skipped={skipped}")


if __name__ == "__main__":
    main()
