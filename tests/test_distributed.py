"""Distributed-path integration tests on forced host devices (subprocess).

Each test spawns a python subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the main pytest
process keeps its single-device view (per the dry-run isolation rule).
"""
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, timeout=420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    """Numerics: the 2x4-sharded train step == unsharded step."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.configs.base import get_arch, reduced
        from repro.launch.mesh import make_host_mesh
        from repro.models.api import build_model, rules_for
        from repro.models import params as PD
        from repro.sharding.specs import set_rules
        from repro.train.loop import make_train_step
        from repro.train.optimizer import AdamWConfig, init_opt_state
        from repro.data import TokenPipeline

        cfg = reduced(get_arch("yi-6b")).replace(n_layers=2)
        model = build_model(cfg)
        params = PD.init_params(model.param_defs(), 0, jnp.float32)
        opt = init_opt_state(params)
        pipe = TokenPipeline(cfg.vocab_size, 32, 8)
        batch = {"tokens": jnp.asarray(pipe.batch_at(0))}

        # unsharded reference
        ref_step = jax.jit(make_train_step(model, AdamWConfig()))
        p1, o1, m1 = ref_step(params, opt, batch)

        mesh = make_host_mesh(2, 4)
        rules = rules_for(cfg, mesh, "train", fsdp=True)
        pspecs = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), PD.specs(model.param_defs(), rules),
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        with mesh, set_rules(mesh, rules):
            step = jax.jit(make_train_step(model, AdamWConfig(),
                                           mesh=mesh, rules=rules),
                           in_shardings=(pspecs, {"m": pspecs, "v": pspecs,
                                         "step": NamedSharding(mesh, jax.sharding.PartitionSpec())},
                                         None))
            sp = jax.device_put(params, pspecs)
            so = {"m": jax.device_put(opt["m"], pspecs),
                  "v": jax.device_put(opt["v"], pspecs), "step": opt["step"]}
            p2, o2, m2 = step(sp, so, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, \
            (float(m1["loss"]), float(m2["loss"]))
        d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree_util.tree_leaves(p1),
                                jax.tree_util.tree_leaves(p2)))
        assert d < 5e-3, d
        print("OK", float(m2["loss"]))
    """)
    assert "OK" in out


def test_compressed_psum_pod():
    """int8-on-the-wire cross-pod mean == f32 mean within quant error."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.train.compression import compressed_psum_pod
        mesh = make_host_mesh(2, 2, pod=2)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 32)).astype(np.float32))
        with mesh:
            y = compressed_psum_pod(x, mesh)
        # replicated input -> mean across pods == x up to int8 quantization
        err = float(jnp.max(jnp.abs(y - x)))
        amax = float(jnp.max(jnp.abs(x)))
        assert err <= amax / 127 + 1e-5, (err, amax / 127)
        print("OK", err)
    """)
    assert "OK" in out


def test_elastic_restore_across_mesh_sizes(tmp_path):
    """Checkpoint on a 2x4 mesh, restore on 4x2: loss continues identically."""
    out = run_sub(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.checkpoint import CheckpointManager
        from repro.configs.base import get_arch, reduced
        from repro.launch.mesh import make_host_mesh
        from repro.models.api import build_model, rules_for
        from repro.models import params as PD
        from repro.sharding.specs import set_rules
        from repro.train.loop import make_train_step
        from repro.train.optimizer import AdamWConfig, init_opt_state
        from repro.data import TokenPipeline

        cfg = reduced(get_arch("qwen3-0.6b")).replace(n_layers=1)
        model = build_model(cfg)
        pipe = TokenPipeline(cfg.vocab_size, 16, 8)
        mgr = CheckpointManager(r"{tmp_path}", async_save=False)

        def make(mesh_shape):
            mesh = make_host_mesh(*mesh_shape)
            rules = rules_for(cfg, mesh, "train", fsdp=False)
            pspecs = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                PD.specs(model.param_defs(), rules),
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
            step = jax.jit(make_train_step(model, AdamWConfig(lr=1e-3),
                                           mesh=mesh, rules=rules))
            return mesh, pspecs, step

        # phase 1: train 2 steps on (2, 4) and checkpoint
        mesh, pspecs, step = make((2, 4))
        params = PD.init_params(model.param_defs(), 0, jnp.float32)
        opt = init_opt_state(params)
        with mesh:
            for s in range(2):
                params, opt, m = step(params, opt,
                                      {{"tokens": jnp.asarray(pipe.batch_at(s))}})
        mgr.save(2, {{"params": params, "opt": opt}})
        ref_params, ref_opt = params, opt
        with mesh:
            _, _, m_ref = step(ref_params, ref_opt,
                               {{"tokens": jnp.asarray(pipe.batch_at(2))}})

        # phase 2: restore onto a (4, 2) mesh — elastic reshard
        mesh2, pspecs2, step2 = make((4, 2))
        _, state = mgr.restore()
        with mesh2:
            p2 = jax.device_put(state["params"], pspecs2)
            o2 = {{"m": jax.device_put(state["opt"]["m"], pspecs2),
                  "v": jax.device_put(state["opt"]["v"], pspecs2),
                  "step": jnp.asarray(state["opt"]["step"])}}
            _, _, m2 = step2(p2, o2, {{"tokens": jnp.asarray(pipe.batch_at(2))}})
        assert abs(float(m_ref["loss"]) - float(m2["loss"])) < 1e-4
        print("OK", float(m2["loss"]))
    """)
    assert "OK" in out


def test_dryrun_cell_mini_multipod():
    """The dry-run machinery itself on an 8-device (2,2,2) pod mesh."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        import repro.launch.mesh as mesh_mod
        # shrink the production mesh to the forced-device pool
        mesh_mod.make_production_mesh = lambda multi_pod=False: (
            jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                          (AxisType.Auto,) * 3)
            if multi_pod else
            jax.make_mesh((2, 4), ("data", "model"), (AxisType.Auto,) * 2))
        import repro.launch.dryrun as dr
        dr.make_production_mesh = mesh_mod.make_production_mesh
        import repro.configs.base as base
        from repro.configs.base import load_all, reduced, ShapeConfig
        archs = load_all()
        small = reduced(archs["qwen3-0.6b"])
        archs["qwen3-0.6b"] = small
        base.SHAPES["mini_train"] = ShapeConfig("mini_train", 64, 8, "train")
        base.SHAPES["mini_decode"] = ShapeConfig("mini_decode", 64, 8, "decode")
        for shape in ("mini_train", "mini_decode"):
            for mp in (False, True):
                res = dr.dryrun_cell("qwen3-0.6b", shape, multi_pod=mp)
                assert res["roofline"]["flops_per_chip"] > 0
                assert res["memory"]["peak_per_device_bytes"] > 0
        print("OK")
    """)
    assert "OK" in out
