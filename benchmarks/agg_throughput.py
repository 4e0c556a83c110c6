"""Aggregation throughput: profiles/sec and peak RSS, old vs zero-copy path.

Measures the streaming aggregator on the standard synthetic workload for
every executor backend, comparing the **legacy** data plane (three-pass
phase 2, pickled plane transport) against the **fused** zero-copy plane
(single-sort kernel, mmap loads, shm slab transport) — and, with
``--compute device|both``, the **device** plane (fused pipeline with the
combine/propagate hot loops routed through the Pallas kernels).  Each
configuration runs in a fresh subprocess so peak RSS (``ru_maxrss``) is
honest — the parent's high-water mark can't leak between measurements.

On a host without an accelerator the device rows run the kernels in
interpret mode and are labeled ``device_mode: "interpret-proxy"`` — they
validate the full dispatch path and feed the parity gate, but their wall
times are NOT accelerator performance.  Rows measured on real hardware are
labeled ``device_mode: "accelerator"``.  Device rows run on the ``serial``
and ``threads`` executors only: one process holds the accelerator, so this
parent never imports JAX and each measurement subprocess is the only one
on the device while it runs.

Emits ``BENCH_agg.json`` with per-config wall time, profiles/sec, peak RSS
the sharded path's peak out-of-order plane residency (``sink_peak``), and a
``device_parity`` block: the device path is re-run serially and on 2 and 4
threads, and its PMS/CMS digests must collapse to a single set.

Standalone usage::

    PYTHONPATH=src python -m benchmarks.agg_throughput [--smoke] \
        [--compute cpu|device|both] [--out BENCH_agg.json] [--check]

``--check`` additionally asserts fused >= 1.5x legacy on the ``processes``
backend (the acceptance bar; skipped in smoke mode, where fixed pool
startup costs dominate the tiny workload) and — when a real accelerator is
present and device rows were measured — that the ``threads`` backend's
device row beats its fused-CPU row (the GIL-release dividend; on the
interpret proxy this check is recorded as skipped, not asserted).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import resource
import subprocess
import sys
import tempfile
import time

# Workload shape: sparse profiles over a huge unified CCT (the paper's
# Table-2 regime — rank-private call paths make the unified tree ~P x any
# single profile's footprint).  This is exactly where the legacy dense
# propagate pays O(n_ctx_unified x m) per profile regardless of how sparse
# the profile is, and where the fused kernel's interval segment sums pay
# only O(x log x).  SMOKE is CI-sized: seconds per config, not minutes.
SMOKE = dict(n_profiles=10, n_ctx=400, ctx_density=0.2, met_density=0.2,
             trace_len=64, n_private=150)
STANDARD = dict(n_profiles=48, n_ctx=4000, ctx_density=0.08,
                met_density=0.1, trace_len=500, n_private=4000)

EXECUTORS = ("serial", "threads", "processes")


def _configs(smoke: bool, compute: str = "cpu"):
    workers = 2 if smoke else 4
    cfgs = []
    for executor in EXECUTORS:
        n_workers = 1 if executor == "serial" else workers
        if compute in ("cpu", "both"):
            for plane in ("legacy", "fused"):
                transport = "pickle" if plane == "legacy" else "shm"
                cfgs.append({
                    "name": f"{executor}-{plane}",
                    "executor": executor,
                    "n_workers": n_workers,
                    "pipeline": plane,
                    "plane_transport": transport,
                    "compute": "cpu",
                })
        if compute in ("device", "both") and executor != "processes":
            cfgs.append({
                "name": f"{executor}-device",
                "executor": executor,
                "n_workers": n_workers,
                "pipeline": "fused",
                "plane_transport": "shm",
                "compute": "device",
            })
    return cfgs


def _digest(path) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _run_single(spec: dict) -> dict:
    """Entry point for the measurement subprocess: one aggregation run."""
    from repro.core.aggregate import AggregationConfig, StreamingAggregator

    paths = spec["paths"]
    cfg = AggregationConfig(executor=spec["executor"],
                            n_workers=spec["n_workers"],
                            pipeline=spec["pipeline"],
                            plane_transport=spec["plane_transport"],
                            compute=spec.get("compute", "cpu"),
                            # no accelerator -> interpret mode, labeled below
                            device_interpret=True)
    t0 = time.perf_counter()
    res = StreamingAggregator(spec["out_dir"], cfg).run(paths)
    wall = time.perf_counter() - t0
    rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    # children (processes backend) report their own high-water mark
    child_kb = resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss
    row = {
        "name": spec["name"],
        "wall_s": wall,
        "profiles_per_s": len(paths) / wall,
        "peak_rss_mib": rss_kb / 1024,
        "peak_child_rss_mib": child_kb / 1024,
        "sink_peak": res.timings.get("sink_peak", 0.0),
        "n_values": res.n_values,
        "pms_bytes": res.sizes["pms"],
    }
    if cfg.compute == "device":
        from repro.kernels import batch
        row["device_mode"] = ("accelerator" if batch.has_accelerator()
                              else "interpret-proxy")
        row["device_launches"] = res.timings.get("device_launches", 0.0)
    if spec.get("digests"):
        row["pms_sha"] = _digest(res.pms_path)
        row["cms_sha"] = _digest(res.cms_path) if res.cms_path else None
    return row


def _spawn_single(spec: dict) -> dict:
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.agg_throughput",
         "--single", json.dumps(spec)],
        capture_output=True, text=True,
        env=dict(os.environ,
                 PYTHONPATH=os.pathsep.join(
                     filter(None, ["src", os.environ.get("PYTHONPATH")]))),
    )
    if proc.returncode != 0:
        raise RuntimeError(f"bench config {spec['name']} failed:\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _parity_gate(paths, td, out) -> dict:
    """The device determinism gate: device runs on 1 (serial), 2 and 4
    threads must produce one (pms, cms) digest set."""
    workers = [1, 2, 4]
    digests = set()
    for w in workers:
        executor = "serial" if w == 1 else "threads"
        spec = {"name": f"parity-device-w{w}", "executor": executor,
                "n_workers": w, "pipeline": "fused", "plane_transport": "shm",
                "compute": "device", "paths": paths,
                "out_dir": f"{td}/parity-w{w}", "digests": True}
        row = _spawn_single(spec)
        digests.add((row["pms_sha"], row["cms_sha"]))
    ok = len(digests) == 1
    out(f"agg.device_parity,0,workers={'|'.join(map(str, workers))};"
        f"ok={str(ok).lower()}")
    if not ok:
        raise AssertionError(
            f"device path not worker-count-deterministic: {len(digests)} distinct "
            f"digest sets across worker counts {workers}")
    return {"workers": workers, "ok": ok}


def run(out=print, tiny: bool = False, check: bool = False,
        json_path: str = "BENCH_agg.json", compute: str = "cpu"):
    rows = []
    with tempfile.TemporaryDirectory() as td:
        from benchmarks.workloads import Workload, generate
        gen = SMOKE if tiny else STANDARD
        w = Workload("agg-bench", gen["n_profiles"], gen["n_ctx"], 8, 40,
                     gen["ctx_density"], gen["met_density"],
                     trace_len=gen["trace_len"], n_private=gen["n_private"])
        paths, _, _ = generate(w, td + "/in", seed=1)

        for cfg in _configs(tiny, compute):
            spec = dict(cfg, paths=paths, out_dir=f"{td}/{cfg['name']}")
            row = _spawn_single(spec)
            rows.append(row)
            mode = (f";device_mode={row['device_mode']}"
                    if "device_mode" in row else "")
            out(f"agg.{row['name']},{row['wall_s']*1e6:.0f},"
                f"profiles_per_s={row['profiles_per_s']:.1f}"
                f";peak_rss_mib={row['peak_rss_mib']:.1f}"
                f";sink_peak={row['sink_peak']:.0f}{mode}")

        device_parity = None
        if compute in ("device", "both"):
            device_parity = _parity_gate(paths, td, out)

    by_name = {r["name"]: r for r in rows}
    speedups = {}
    if compute in ("cpu", "both"):
        for executor in EXECUTORS:
            legacy = by_name[f"{executor}-legacy"]
            fused = by_name[f"{executor}-fused"]
            speedups[executor] = legacy["wall_s"] / fused["wall_s"]
            out(f"agg.speedup_{executor},0,"
                f"fused_over_legacy={speedups[executor]:.2f}")
    device_speedups = {}
    if compute == "both":
        for executor in EXECUTORS:
            if f"{executor}-device" not in by_name:
                continue
            fused = by_name[f"{executor}-fused"]
            device = by_name[f"{executor}-device"]
            device_speedups[executor] = fused["wall_s"] / device["wall_s"]
            out(f"agg.speedup_{executor},0,"
                f"device_over_fused={device_speedups[executor]:.2f}")

    report = {"workload": "smoke" if tiny else "standard",
              "configs": rows, "fused_speedup": speedups}
    if device_speedups:
        report["device_speedup"] = device_speedups
    if device_parity is not None:
        report["device_parity"] = device_parity
    with open(json_path, "w") as f:
        json.dump(report, f, indent=2)
    out(f"agg.report,0,json={json_path}")

    if check and not tiny and speedups:
        assert speedups["processes"] >= 1.5, (
            f"fused pipeline speedup on processes backend "
            f"{speedups['processes']:.2f}x < 1.5x acceptance bar")
    if check and device_speedups:
        if by_name["threads-device"].get("device_mode") == "accelerator":
            assert device_speedups["threads"] > 1.0, (
                f"threads device row {device_speedups['threads']:.2f}x does "
                f"not improve on the fused-CPU threads baseline despite an "
                f"accelerator being present")
        else:
            out("agg.check_threads_device,0,skipped=interpret-proxy")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized workload")
    ap.add_argument("--check", action="store_true",
                    help="assert the 1.5x processes-backend speedup (and the "
                         "threads device win when an accelerator is present)")
    ap.add_argument("--compute", default="cpu",
                    choices=["cpu", "device", "both"],
                    help="which data planes to measure; device rows use the "
                         "interpret proxy when no accelerator is attached")
    ap.add_argument("--out", default="BENCH_agg.json")
    ap.add_argument("--single", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.single is not None:
        print(json.dumps(_run_single(json.loads(args.single))))
        return
    run(tiny=args.smoke, check=args.check, json_path=args.out,
        compute=args.compute)


if __name__ == "__main__":
    main()
