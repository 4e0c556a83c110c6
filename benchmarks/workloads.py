"""Synthetic measurement workloads shaped like the paper's case studies.

Each named workload reproduces the density structure of a paper row
(Table 1/2): context density = fraction of an application's contexts a
profile observes with non-zero metrics; metric density = fraction of
enabled metrics with non-zero values within a non-empty context.  The
CPU/GPU metric split is modeled by giving even workers ("CPU threads")
host metrics and odd workers ("GPU streams") device metrics — exactly the
disjoint-code-region sparsity the paper describes.
"""
from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

from repro.core.cct import KIND_LINE, KIND_MODULE, KIND_OP, KIND_PHASE, ContextTree
from repro.core.sparse import MeasurementProfile, SparseMetrics, Trace


@dataclass(frozen=True)
class Workload:
    name: str
    n_profiles: int
    n_ctx: int               # application context count
    n_cpu_metrics: int
    n_gpu_metrics: int
    ctx_density: float       # paper Table 1 "Contexts" column
    met_density: float       # paper Table 1 "Metrics" column
    trace_len: int = 0
    n_private: int = 0       # per-profile private contexts (rank-specific
                             # call paths / reconstructed GPU routes) — the
                             # source of paper Table 2's unified-CCT sparsity


# paper Table 1 rows (density columns), scaled to container-sized runs
TABLE1_WORKLOADS = [
    Workload("AMG2013(1)", 48, 3000, 1, 0, 0.691, 1.00),
    Workload("AMG2013(7)", 48, 3000, 7, 0, 0.227, 0.207),
    Workload("PeleC(1+82)", 48, 3000, 1, 82, 0.206, 0.019),
    Workload("Nyx(1+62)", 48, 3000, 1, 62, 0.096, 0.028),
]

# Table 2 runs: same densities per profile, but each rank/stream also owns
# private contexts, so the unified tree is ~P x larger than any single
# profile's footprint (per-thread call paths, inlined/loop expansion,
# reconstructed GPU routes — paper §3.3/§4.1)
TABLE2_WORKLOADS = [
    Workload("AMG2013(1)", 64, 1200, 1, 0, 0.08, 1.00, n_private=400),
    Workload("AMG2013(7)", 96, 1200, 7, 0, 0.04, 0.207, n_private=800),
    Workload("PeleC(1+82)", 96, 1200, 1, 82, 0.04, 0.019, n_private=700),
    Workload("Nyx(1+62)", 96, 1200, 1, 62, 0.03, 0.028, n_private=700),
]


def build_app_tree(n_ctx: int, rng) -> ContextTree:
    """Application-shaped tree: phases -> modules -> ops -> lines."""
    t = ContextTree()
    phases = [t.child(0, KIND_PHASE, p) for p in ("main", "solve", "comm")]
    mods = [t.child(phases[i % 3], KIND_MODULE, f"mod{i}") for i in range(24)]
    ops = []
    while len(t) < n_ctx * 0.6:
        ops.append(t.child(mods[int(rng.integers(0, len(mods)))], KIND_OP,
                           f"fn{len(ops)}"))
    while len(t) < n_ctx:
        parent = ops[int(rng.integers(0, len(ops)))]
        t.child(parent, KIND_LINE, f"line{len(t)}")
    return t


def generate(w: Workload, out_dir: str, seed: int = 0,
             counts: bool = False) -> list[str]:
    """Write ``w``'s profiles under ``out_dir``; values are exponential
    costs, or with ``counts`` small integer sample counts (1..15)."""
    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.default_rng(seed)
    shared = build_app_tree(w.n_ctx, rng)
    n_metrics = w.n_cpu_metrics + w.n_gpu_metrics
    paths = []
    for p in range(w.n_profiles):
        # per-profile tree = shared structure (+ rank-private call paths)
        tree = ContextTree.from_arrays(shared.to_arrays())
        priv = []
        if w.n_private:
            base = tree.child(0, KIND_PHASE, "worker")
            own = tree.child(base, KIND_MODULE, f"rank{p}")
            for i in range(w.n_private):
                priv.append(tree.child(own, KIND_LINE, f"p{p}.{i}"))
        n_ctx = len(tree)
        is_gpu = (p % 2 == 1) and w.n_gpu_metrics > 0
        if is_gpu:
            mids_pool = np.arange(w.n_cpu_metrics, n_metrics)
        else:
            mids_pool = np.arange(0, w.n_cpu_metrics)
        n_live_ctx = max(int(len(shared) * w.ctx_density), 1)
        live_ctx = rng.choice(len(shared), size=n_live_ctx, replace=False)
        if priv:
            live_ctx = np.concatenate([live_ctx, np.asarray(priv)])
        k = max(int(len(mids_pool) * min(w.met_density * n_metrics
                                         / max(len(mids_pool), 1), 1.0)), 1)
        ctxs, mids, vals = [], [], []
        for c in live_ctx:
            sel = rng.choice(mids_pool, size=min(k, len(mids_pool)),
                             replace=False)
            ctxs.extend([c] * len(sel))
            mids.extend(sel.tolist())
            vals.extend((rng.integers(1, 16, len(sel)) if counts
                         else rng.exponential(1.0, len(sel))).tolist())
        sm = SparseMetrics.from_triplets(ctxs, mids, vals)
        trace = Trace.empty()
        if w.trace_len:
            trace = Trace(np.sort(rng.uniform(0, 60, w.trace_len)),
                          rng.choice(live_ctx, w.trace_len).astype(np.uint32))
        prof = MeasurementProfile(
            environment={"app": w.name, "n_metrics": n_metrics},
            identity={"rank": p // 2, "stream": p % 2,
                      "kind": "gpu" if is_gpu else "cpu"},
            file_paths=[], tree=tree, trace=trace, metrics=sm)
        path = os.path.join(out_dir, f"{w.name}.{p:04d}.rprf")
        prof.save(path)
        paths.append(path)
    return paths, len(shared), n_metrics


def generate_timing_workload(out_dir: str, *, n_profiles=96, n_ctx=4000,
                             n_metrics=32, trace_len=4000, seed=1,
                             n_private=400):
    # per-rank private contexts make the unified CCT ~P x larger than any
    # profile (the exascale effect that makes dense analysis intractable)
    w = Workload("LMP-like", n_profiles, n_ctx, 2, n_metrics - 2,
                 0.15, 0.05, trace_len=trace_len, n_private=n_private)
    return generate(w, out_dir, seed)
