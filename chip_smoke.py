#!/usr/bin/env python3
"""Smoke test: the postmortem aggregator's device path on one TPU chip.

Generates the paper's Table 2 PeleC(1+82) fleet from a seed — 96 profiles,
48 CPU ranks with 1 metric and 48 GPU streams drawing from 82 metrics, each
with 4,096 private contexts, so the unified tree holds ~394k contexts — and
runs ``repro.launch.analyze --executor threads --workers 4 --compute device``
on it in this process, the one that holds the chip.  The same fleet through
``--compute cpu`` (the numpy path) is the reference:

* float fleet: PMS planes and summary statistics agree within the f32 bound
  of the device dtype contract (``repro.kernels.batch``);
* a second fleet of the same shape whose values are small integer sample
  counts (every plane "exact" class): ``db.pms`` and ``db.cms`` are
  byte-identical;
* ``analyze query`` topk and stripe answers agree;
* the kernels ran on the device and compiled: the combine, propagation,
  census and offset-scan launch counters are positive and no kernel was
  traced in interpret mode.

Usage::

    python chip_smoke.py                                        # one TPU chip
    JAX_PLATFORMS=cpu python chip_smoke.py --interpret --tiny   # CPU rehearsal

Without a TPU, and without ``--interpret``, it exits non-zero and names the
platform JAX found.  Every line but the last is smoke output, not a
benchmark number; the last line is the JSON result
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

from benchmarks.workloads import TABLE2_WORKLOADS, generate  # noqa: E402
from repro.core.metrics import INCLUSIVE_BIT  # noqa: E402
from repro.core.pms import PMSReader  # noqa: E402
from repro.launch import analyze  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

PELEC = next(w for w in TABLE2_WORKLOADS if w.name == "PeleC(1+82)")
# 4,096 private contexts: 4,144 values per profile, at or above the size
# from which the duplicate-key combine runs on the device
# (batch.DEVICE_COMBINE_MIN = 4,096)
FULL = dataclasses.replace(PELEC, n_private=4096)
TINY = dataclasses.replace(PELEC, n_profiles=8, n_ctx=300, n_private=200)
WORKERS = 4
KERNELS = ("blockscan", "segstats", "scatter_add")
U = 2.0 ** -24  # f32 unit roundoff


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(label: str, **fields) -> None:
    print(json.dumps({"smoke_output": label, **fields}, default=float),
          flush=True)


class CompileLog:
    """Backend compile seconds (a persistent-cache hit included) and cache
    hits/misses, from JAX's monitoring events."""

    def __init__(self, jax):
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def reset(self) -> None:
        self.compile_s, self.hits, self.misses = 0.0, 0, 0

    def _duration(self, event, duration, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"backend_compile_s": self.compile_s,
                "cache_hits": self.hits, "cache_misses": self.misses}


def cli(argv: list[str]) -> dict:
    """``repro.launch.analyze`` in this process; its JSON document."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        analyze.main(argv)
    return json.loads(buf.getvalue())


def aggregate(paths, out: str, compute: str, interpret: bool) -> dict:
    argv = [*paths, "--out", out, "--executor", "threads",
            "--workers", str(WORKERS), "--compute", compute]
    if compute == "device" and interpret:
        argv.append("--device-interpret")
    t0 = time.perf_counter()
    summary = cli(argv)
    summary["wall_s"] = time.perf_counter() - t0
    return summary


def same_bytes(a: str, b: str) -> bool:
    return Path(a).read_bytes() == Path(b).read_bytes()


def _keys(rows, mids) -> np.ndarray:
    return (rows.astype(np.int64) << 16) | mids.astype(np.int64)


def compare_float_planes(dev: PMSReader, cpu: PMSReader, factor: float
                         ) -> dict:
    """Every value within ``factor * T`` of the CPU's, where T is the sum
    of |exclusive values| of that profile's metric: the f32 error bound of
    an inclusive sum taken as a difference of two device prefix sums.  A
    key on one side only must be that small too (an inclusive sum that
    rounded to 0).  Returns per-metric max T and the counts seen."""
    t_max = np.zeros(INCLUSIVE_BIT)
    worst, one_sided, vanished = 0.0, 0, {}
    for p in range(cpu.n_profiles):
        cr, cm, cv = cpu.plane(p).triplets()
        dr, dm, dv = dev.plane(p).triplets()
        excl = cm < INCLUSIVE_BIT
        t = np.bincount(cm[excl], weights=np.abs(cv[excl]),
                        minlength=INCLUSIVE_BIT)
        t_max = np.maximum(t_max, t)
        ck, dk = _keys(cr, cm), _keys(dr, dm)
        _, ic, id_ = np.intersect1d(ck, dk, assume_unique=True,
                                    return_indices=True)
        tol = factor * t[cm[ic] & (INCLUSIVE_BIT - 1)]
        err = np.abs(dv[id_] - cv[ic])
        check(np.all(err <= tol),
              f"profile {p}: {int(np.sum(err > tol))} values outside the "
              f"f32 bound (worst {float(np.max(err - tol))} over)")
        worst = max(worst, float(np.max(err / np.maximum(tol / factor, 1e-300),
                                        initial=0.0)))
        for keys, mids, vals in ((ck, cm, cv), (dk, dm, dv)):
            only = ~np.isin(keys, ck if keys is dk else dk)
            check(np.all(np.abs(vals[only])
                         <= factor * t[mids[only] & (INCLUSIVE_BIT - 1)]),
                  f"profile {p}: a key on one side only is not near zero")
            one_sided += int(only.sum())
        for k in ck[~np.isin(ck, dk)]:
            vanished[int(k)] = vanished.get(int(k), 0) + 1
    return {"t_max": t_max, "worst_err_over_T": worst,
            "one_sided_keys": one_sided, "vanished": vanished}


def compare_float_stats(dev: PMSReader, cpu: PMSReader, factor: float,
                        planes: dict) -> int:
    """Summary statistics per (context, metric): the device's keys are the
    CPU's, counts differ by exactly the values that rounded to 0, and sum,
    mean, min and max agree within the per-profile bound (times the count
    for the sum).  Returns how many keys lost a value to rounding."""
    ks, kd = (_keys(s.stats["ctx"], s.stats["mid"]) for s in (cpu, dev))
    check(np.all(np.isin(kd, ks)), "device statistics hold a key the CPU's "
                                   "do not")
    _, ic, id_ = np.intersect1d(ks, kd, assume_unique=True,
                                return_indices=True)
    cs = {f: cpu.stats[f][ic] for f in ("sum", "count", "mean", "min", "max")}
    ds = {f: dev.stats[f][id_] for f in cs}
    lost = np.array([planes["vanished"].get(int(k), 0) for k in ks[ic]])
    check(np.array_equal(cs["count"] - ds["count"], lost),
          "statistics counts differ by more than the values rounded to 0")
    mids = cpu.stats["mid"][ic].astype(np.int64) & (INCLUSIVE_BIT - 1)
    tol = factor * planes["t_max"][mids]
    full = lost == 0
    for f, scale in (("sum", cs["count"]), ("mean", 1.0), ("min", 1.0),
                     ("max", 1.0)):
        bound = (tol * scale)[full] if f == "sum" else tol[full]
        err = np.abs(ds[f] - cs[f])[full]
        check(np.all(err <= bound), f"statistic {f!r} outside the f32 bound")
    return int((~full).sum())


def compare_queries(db_dev: str, db_cpu: str, exact: bool, tol: float
                    ) -> list[str]:
    """topk (inclusive, metrics 0 and 1), then the stripes of the root and
    of metric 1's hottest exclusive context: the same answers (within
    ``tol`` on a float fleet, near-ties allowed to swap places)."""
    asked = []

    def both(*argv):
        asked.append(" ".join(argv))
        return (cli(["query", db_dev, *argv]), cli(["query", db_cpu, *argv]))

    def close(a, b) -> bool:
        return a == b if exact else abs(a - b) <= tol

    for metric in ("0", "1"):
        d, c = both("topk", "--metric", metric, "-k", "10")
        if exact:
            check(d == c, f"topk metric {metric} differs")
            continue
        dv = [r["value"] for r in d["rows"]]
        cv = [r["value"] for r in c["rows"]]
        check(len(dv) == len(cv) and all(map(close, dv, cv)),
              f"topk metric {metric} values differ")
        cpos = {r["ctx"]: i for i, r in enumerate(c["rows"])}
        for i, r in enumerate(d["rows"]):
            j = cpos.get(r["ctx"], len(cv) - 1)
            check(abs(cv[i] - cv[j]) <= 2 * tol,
                  f"topk metric {metric} ranks differ beyond a near-tie")
    hot = cli(["query", db_cpu, "topk", "--metric", "1", "-k", "1",
               "--exclusive"])["rows"][0]["ctx"]
    for ctx, metric, extra in (("0", "0", ["--inclusive"]),
                               (str(hot), "1", [])):
        d, c = both("stripe", "--ctx", ctx, "--metric", metric, *extra)
        check(d["profiles"] == c["profiles"]
              and all(map(close, d["values"], c["values"])),
              f"stripe ctx {ctx} metric {metric} differs")
    return asked


def device_evidence(summaries, on_chip: bool, traced: dict) -> dict:
    launches = {k: min(s["timings"].get(f"device_{k}_launches", 0.0)
                       for s in summaries)
                for k in ("inclusive", "combine", "census", "scan")}
    # in interpret mode the combine and the census stay on numpy
    # (DeviceAggregator.offload_combine, batch.device_census_counts)
    needed = launches if on_chip else {k: launches[k]
                                       for k in ("inclusive", "scan")}
    check(all(v > 0 for v in needed.values()),
          f"a device phase never launched: {launches}")
    mode = "compiled" if on_chip else "interpret"
    modes = {k: sorted(v) for k, v in traced.items()}
    check(all(v == [mode] for v in modes.values()),
          f"kernels traced in the wrong mode (want {mode}): {modes}")
    if on_chip:
        check(set(KERNELS) <= set(modes), f"a kernel never ran: {modes}")
    return {"launches": launches, "kernel_modes": modes}


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--interpret", action="store_true",
                    help="rehearsal: allow the CPU backend, with the kernels "
                         "in interpret mode")
    ap.add_argument("--tiny", action="store_true",
                    help="a small fleet of the same shape (rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.interpret:
        print(f"chip_smoke: no TPU: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}); pass --interpret to rehearse on the "
              f"CPU", file=sys.stderr)
        return 2
    on_chip = dev.platform == "tpu"
    cache_dir = enable_compile_cache()
    compiles = CompileLog(jax)
    from repro.kernels import ops
    from repro.kernels.blockscan import DEFAULT_BLOCK_N

    fleet = TINY if args.tiny else FULL
    say("start", device=device, fleet=fleet.name,
        profiles=fleet.n_profiles, private_contexts=fleet.n_private,
        cache_dir=cache_dir, cache_entries=cache_entries(cache_dir),
        note="smoke output, not benchmark numbers")
    with tempfile.TemporaryDirectory(prefix="chip_smoke.") as td:
        t0 = time.perf_counter()
        fpaths, _, _ = generate(fleet, f"{td}/float", seed=args.seed)
        ipaths, _, _ = generate(fleet, f"{td}/int", seed=args.seed + 1,
                                counts=True)
        say("fleets generated", seconds=time.perf_counter() - t0)

        runs = {}
        for name, paths, compute in (
                ("float-device-cold", fpaths, "device"),
                ("float-device-warm", fpaths, "device"),
                ("float-cpu", fpaths, "cpu"),
                ("int-device", ipaths, "device"),
                ("int-cpu", ipaths, "cpu")):
            if name.endswith("warm"):
                jax.clear_caches()  # recompile: from the persistent cache
            compiles.reset()
            runs[name] = aggregate(paths, f"{td}/{name}", compute,
                                   args.interpret)
            say("analyze summary", run=name, summary=runs[name],
                compile=compiles.snapshot(),
                cache_entries=cache_entries(cache_dir))
        n_ctx = runs["float-cpu"]["contexts"]
        check(all(r["contexts"] == n_ctx for n, r in runs.items()
                  if n.startswith("float")), "context counts differ")

        db = {n: str(Path(r["pms"]).parent) for n, r in runs.items()}
        pms = {n: r["pms"] for n, r in runs.items()}
        cms = {n: r["cms"] for n, r in runs.items()}
        check(same_bytes(pms["int-device"], pms["int-cpu"])
              and same_bytes(cms["int-device"], cms["int-cpu"]),
              "integer fleet: device db.pms/db.cms not byte-identical to cpu")
        check(same_bytes(pms["float-device-cold"], pms["float-device-warm"]),
              "float fleet: two device runs differ")

        # longest f32 add chain of a device inclusive value: log2(block)
        # in-block steps and one carry per block, for each of the two
        # prefix sums it is the difference of, plus the input rounding
        adds = math.log2(DEFAULT_BLOCK_N) + math.ceil(n_ctx / DEFAULT_BLOCK_N)
        factor = (2 * adds + 4) * U
        with PMSReader(pms["float-device-cold"]) as d, \
                PMSReader(pms["float-cpu"]) as c:
            planes = compare_float_planes(d, c, factor)
            lost = compare_float_stats(d, c, factor, planes)
        tol = fleet.n_profiles * factor * float(planes["t_max"].max())
        asked = compare_queries(db["float-device-cold"], db["float-cpu"],
                                exact=False, tol=tol)
        compare_queries(db["int-device"], db["int-cpu"], exact=True, tol=0.0)
        say("agreement", float_bound_factor_over_u=factor / U,
            worst_float_err_in_units_of_u_T=planes["worst_err_over_T"] / U,
            float_one_sided_keys=planes["one_sided_keys"],
            float_stat_keys_with_a_value_rounded_to_0=lost,
            int_fleet="db.pms and db.cms byte-identical", queries=asked)

        device_runs = [r for n, r in runs.items() if "device" in n]
        evidence = device_evidence(device_runs, on_chip, ops.TRACED_MODES)
        check(cache_entries(cache_dir) > 0,
              f"nothing was written to the compile cache {cache_dir}")
        stats = dev.memory_stats() or {}
        say("device", **evidence,
            peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"),
            bytes_limit=stats.get("bytes_limit", "not reported"))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
