"""Measurement subsystem: HLO attribution + end-to-end profile -> analysis."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import load_all, reduced
from repro.core.aggregate import AggregationConfig, StreamingAggregator
from repro.core.metrics import INCLUSIVE_BIT
from repro.core.pms import PMSReader
from repro.data import TokenPipeline
from repro.models import params as P
from repro.models.api import build_model
from repro.profiling import Profiler
from repro.profiling import hlo_attrib
from repro.train.loop import Trainer, TrainerConfig, make_train_step
from repro.train.optimizer import AdamWConfig

ARCHS = load_all()


def test_hlo_parse_and_shape_bytes():
    assert hlo_attrib.shape_bytes("bf16[4,128]{1,0}") == 4 * 128 * 2
    assert hlo_attrib.shape_bytes("(f32[8], s32[2])") == 32 + 8
    hlo = '''
  %dot.1 = f32[16,32]{1,0} dot(%a, %b), metadata={op_name="jit(step)/model/layers/attn/dot_general" source_file="x.py"}
  %add.2 = f32[16,32]{1,0} add(%dot.1, %c), metadata={op_name="jit(step)/model/layers/mlp/add"}
  %p = f32[16]{0} parameter(0)
'''
    recs = hlo_attrib.parse_hlo(hlo)
    assert len(recs) == 2
    assert recs[0].opcode == "dot" and "attn" in recs[0].scope
    agg = hlo_attrib.attribute(hlo)
    assert sum(v["bytes"] for v in agg.values()) == 2 * 16 * 32 * 4


def test_attribution_from_real_compiled_step():
    cfg = reduced(ARCHS["qwen3-0.6b"]).replace(n_layers=1)
    model = build_model(cfg)
    params = P.init_params(model.param_defs(), 0, jnp.float32)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}
    txt = jax.jit(model.loss_fn).lower(params, batch).compile().as_text()
    recs = hlo_attrib.parse_hlo(txt)
    assert len(recs) > 10
    scopes = {r.scope for r in recs if r.scope}
    assert scopes, "op_name metadata missing from compiled HLO"
    # fusions resolve their fused computations (reconstruction input)
    fusions = [r for r in recs if r.opcode == "fusion"]
    assert fusions and all(f.calls for f in fusions)


def test_profiler_end_to_end_through_aggregation(tmp_path):
    """Train a tiny model on 2 simulated workers; profile; aggregate;
    verify host/device metric sparsity and inclusive rollups."""
    cfg = reduced(ARCHS["qwen3-0.6b"]).replace(n_layers=1)
    model = build_model(cfg)
    paths = []
    for worker in range(2):
        prof = Profiler({"rank": worker, "stream": 0,
                         "kind": "device" if worker else "host"})
        pipe = TokenPipeline(cfg.vocab_size, 16, 2, seed=worker)
        tr = Trainer(model, AdamWConfig(), TrainerConfig(), pipe, profiler=prof)
        params, opt = tr.init_state(seed=worker)
        # attribute the compiled step's device costs (device-metric analog)
        compiled = jax.jit(make_train_step(model, AdamWConfig())).lower(
            params, opt, {"tokens": jnp.asarray(pipe.batch_at(0))}).compile()
        ca = compiled.cost_analysis()
        prof.attribute_compiled(compiled.as_text(),
                                measured={"flops": ca.get("flops", 0.0)},
                                struct_dir=str(tmp_path / "structs"))
        tr.run(params, opt, steps=2)
        p = str(tmp_path / f"w{worker}.rprf")
        prof.finish(p)
        paths.append(p)

    res = StreamingAggregator(tmp_path / "out", AggregationConfig(n_threads=2)).run(paths)
    with PMSReader(res.pms_path) as r:
        # the unified tree contains host phases AND device op scopes
        names = {r.tree.name_of(c) for c in range(len(r.tree.parent))}
        assert {"train", "data"} <= names
        reg = {m["name"]: m["mid"] for m in r.meta["registry"]}
        plane0 = r.plane(0)
        # host metric present at the train phase context
        train_ctx = [c for c in range(len(r.tree.parent))
                     if r.tree.name_of(c) == "train"][0]
        assert plane0.lookup(train_ctx, reg["host.step_time"]) > 0
        # inclusive device bytes at root == sum over all op contexts
        root_incl = plane0.lookup(0, reg["dev.bytes_hbm"] | INCLUSIVE_BIT)
        rows, mids, vals = plane0.triplets()
        excl = vals[(mids == reg["dev.bytes_hbm"])].sum()
        assert np.isclose(root_incl, excl, rtol=1e-9)
        # natural sparsity: host metrics never appear on op contexts
        op_ctxs = [c for c in range(len(r.tree.parent))
                   if r.tree.kind[c] == 4]
        assert op_ctxs
        for c in op_ctxs[:20]:
            assert plane0.lookup(c, reg["host.step_time"]) == 0.0
