#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json`` on the chip this process
holds.

    python3 bench/run.py --workload pelec-1x82.analyze --seed 7 \
        --seconds 51 --trace 0

A cell names a configuration (a fleet of profiles, ``bench/configs/``) and
a traffic mix (the analysis job, ``bench/mixes/<traffic>.json``).  Set-up
draws the fleet from ``--seed`` and writes it, runs one untimed analysis of
it and warms the propagation's column classes, so that the window compiles
nothing.  The window then runs ``repro.launch.analyze`` in this process on
the fleet, back to back, starting analyses until ``--seconds`` have passed.
Each database is hashed, and deleted once a later one is complete; the
last one is read against the plain reference (``bench/reference.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (profiles), ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones, each read by
``bench/metrics/<name>.py``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: every number compared, beside its limit.  The same
numbers end standard error.

Without a TPU (or fewer chips than the cell asks for) it exits 2 and prints
no result; ``--device-interpret`` lets a rehearsal run the kernels in
interpret mode on the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference  # noqa: E402
from bench.dbread import read_database  # noqa: E402
from bench.fleets import FleetShape, write_fleet  # noqa: E402
from bench.trace_reduce import SPAN, find_xplane, reduce_file  # noqa: E402


def say(label: str, **fields) -> None:
    print(json.dumps({"bench": label, **fields}), flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_cell(benchmark: Path, workload: str) -> Cell:
    spec = json.loads(benchmark.read_text())
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"bench: no workload {workload!r} in {benchmark}")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    mix = json.loads((BENCH / "mixes" / f"{cell['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads", [workload])]

    return Cell(workload, int(cell["chips"]), config, mix,
                mine(spec["end_to_end"]), mine(spec["per_layer"]))


def load_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(run)``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileLog:
    """Compile requests (each a backend compile or a persistent-cache read)
    and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self, jax):
        self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def compiled(self) -> int:
        """Requests the cache did not answer: real compiles."""
        return self.requests - self.hits


class MemorySampler:
    """The most device memory in use on any of ``devices`` while the window
    runs, sampled every ``period`` seconds: the process-wide peak would also
    count set-up's warm-ups, which reach column classes the window may not."""

    def __init__(self, devices, period: float = 0.005):
        self.devices, self.period = devices, period
        self.peak: int | None = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._sample, daemon=True)

    def _sample(self) -> None:
        while True:
            for d in self.devices:
                used = (d.memory_stats() or {}).get("bytes_in_use")
                if used is not None:
                    self.peak = max(self.peak or 0, int(used))
            if self._stop.wait(self.period):
                return

    def __enter__(self) -> "MemorySampler":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: Cell
    device: dict
    fleet: object
    setup_s: float = 0.0
    window_s: float = 0.0
    analyses: list = dataclasses.field(default_factory=list)
    db_bytes: int = 0
    reference: object = None
    trace_file: str | None = None
    trace: dict | None = None
    peaks: dict | None = None

    @property
    def done(self) -> list[dict]:
        """The analyses that completed."""
        return [a for a in self.analyses if a["ok"]]

    def mean_timing(self, key: str) -> float | None:
        vals = [a["summary"]["timings"][key] for a in self.done
                if key in a["summary"]["timings"]]
        return sum(vals) / len(vals) if vals else None


def analyze_argv(paths, out: str, mix: dict, interpret: bool) -> list[str]:
    argv = [*paths, "--out", out, "--executor", mix["executor"],
            "--workers", str(mix["workers"]), "--compute", mix["compute"]]
    if not mix.get("cms", True):
        argv.append("--no-cms")
    if not mix.get("traces", True):
        argv.append("--no-traces")
    if interpret and mix["compute"] == "device":
        argv.append("--device-interpret")
    return argv


def analyze_once(analyze, argv) -> dict:
    """``repro.launch.analyze`` in this process: its JSON summary."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        analyze.main(argv)
    return json.loads(buf.getvalue())


def column_classes(widths: list[int], workers: int) -> list[int]:
    """Column counts of every propagation launch the mix can make: a launch
    coalesces the requests of up to ``workers`` threads, each one profile's
    distinct metrics, padded to the next power of two of at least 8 (the
    device batching's shape classes)."""
    sums = {0}
    for _ in range(workers):
        sums |= {s + w for s in sums for w in set(widths)}
    classes = set()
    for s in sums - {0}:
        b = 8
        while b < s:
            b *= 2
        classes.add(b)
    return sorted(classes)


def warm_columns(n_ctx: int, widths: list[int], workers: int) -> list[int]:
    """Compile (or read from the cache) the propagation at every column
    class the window can meet; the warm analysis alone may miss some,
    since which requests share a launch depends on thread timing."""
    import numpy as np

    from repro.kernels.batch import DeviceAggregator

    agg = DeviceAggregator(np.arange(1, n_ctx + 1))
    classes = column_classes(widths, workers)
    for m in classes:
        agg.inclusive(np.zeros((n_ctx, m), np.float32))
    return classes


def trace_options(jax):
    """Device events and the benchmark's own span: no Python function
    tracing (it slowed a traced analysis several times over on the chip)
    and no HLO protos."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def file_md5(path: str | None) -> str:
    if not path:
        return ""
    h = hashlib.md5()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 22), b""):
            h.update(block)
    return h.hexdigest()


def phases_of(summary: dict) -> list[tuple[str, float, float]]:
    """Host phases rebuilt from consecutive timers, as offsets from the
    start of the analysis span."""
    t = summary["timings"]
    out, a = [], 0.0
    for name in ("phase1", "phase2", "completion"):
        out.append((name, a, a + t.get(name, 0.0)))
        a += t.get(name, 0.0)
    return out


def run_window(jax, analyze, run: Run, args, work: str) -> None:
    """Analyses back to back until ``args.seconds`` have passed; the first
    one under the profiler with ``--trace 1``.  Each complete database is
    hashed, and deleted once a later one is complete."""
    trace_dir = os.path.join(work, "trace")
    last = None
    t0 = time.perf_counter()
    deadline = t0 + args.seconds
    while time.perf_counter() < deadline:
        out = os.path.join(work, f"db{len(run.analyses)}")
        argv = analyze_argv(run.fleet.paths, out, run.cell.mix,
                            args.device_interpret)
        traced = args.trace == 1 and not run.analyses
        if traced:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=trace_options(jax))
        t = time.perf_counter()
        try:
            with (jax.profiler.TraceAnnotation(SPAN) if traced
                  else contextlib.nullcontext()):
                summary = analyze_once(analyze, argv)
            record = {"ok": True, "wall_s": time.perf_counter() - t,
                      "summary": summary,
                      "md5": (file_md5(summary["pms"]), file_md5(summary["cms"]))}
        except Exception as e:  # an analysis that raised: its profiles fail
            record = {"ok": False, "wall_s": time.perf_counter() - t,
                      "error": repr(e)}
            print(f"bench: analysis {len(run.analyses)} failed: {e!r}",
                  file=sys.stderr)
            traceback.print_exc()
        if traced:
            jax.profiler.stop_trace()
            run.trace_file = find_xplane(trace_dir)
        run.analyses.append(record)
        if record["ok"]:
            if last is not None:
                shutil.rmtree(last, ignore_errors=True)
            last = out
        else:
            shutil.rmtree(out, ignore_errors=True)
    end = time.perf_counter()
    run.window_s = end - t0
    say("window", analyses=len(run.analyses), seconds=run.window_s,
        past_deadline_s=end - deadline,
        analysis_s=[a["wall_s"] for a in run.analyses])


def check(run: Run) -> dict:
    """The last complete database against the plain reference, and every
    complete database's bytes against the last one's."""
    numbers = dict.fromkeys(reference.NUMBERS)
    if not run.done:
        return numbers
    t = time.perf_counter()
    final = run.done[-1]
    pms, cms = final["summary"]["pms"], final["summary"]["cms"]
    run.db_bytes = sum(os.path.getsize(p) for p in (pms, cms) if p)
    run.reference = reference.build(run.fleet)
    numbers.update(reference.compare(run.reference, read_database(pms, cms)))
    numbers["repeat_mismatch"] = float(sum(a["md5"] != final["md5"]
                                           for a in run.done))
    say("check", seconds=time.perf_counter() - t)
    return numbers


def read_trace(run: Run) -> None:
    """The traced analysis's device metrics, and the chip's peaks."""
    first = run.analyses[0] if run.analyses else None
    if run.trace_file and first and first["ok"]:
        run.trace = reduce_file(run.trace_file, phases_of(first["summary"]))
    if run.device["platform"] != "tpu":
        return
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if run.device["kind"] not in peaks:
        raise SystemExit(f"bench: no peaks for {run.device['kind']!r} in "
                         f"bench/peaks.json")
    run.peaks = peaks[run.device["kind"]]
    if run.trace:
        run.device["busy_s"] = run.trace["busy_s"]
        run.device["window_s"] = run.trace["window_s"]


def report(run: Run, numbers: dict, trace: bool) -> None:
    """The checks on standard error, then the result line."""
    limits = run.cell.config["limits"]
    failed = sum(len(run.fleet.paths) for a in run.analyses if not a["ok"])
    metrics = {}
    for m in (run.cell.per_layer if trace else run.cell.end_to_end):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(run.done) and not failed
              and reference.verdict(numbers, limits),
              "attempted": len(run.fleet.paths) * len(run.analyses),
              "failed": failed, "metrics": metrics, "device": run.device}
    if run.trace:
        result["breakdown"] = {"device_ops": run.trace["top_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["checks"] = {k: {"value": numbers.get(k), "limit": v}
                        for k, v in limits.items()}
    for k, v in limits.items():
        print(f"check {k} {numbers.get(k)} limit {v}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark", default=str(ROOT / "BENCHMARK.json"),
                    help="the benchmark file naming the cell")
    ap.add_argument("--device-interpret", action="store_true",
                    help="rehearsal: allow the CPU backend, with the kernels "
                         "in interpret mode")
    args = ap.parse_args(argv)
    cell = load_cell(Path(args.benchmark), args.workload)

    # the compile cache lives at a fixed place inside this checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu" and not args.device_interpret:
        print(f"bench: no TPU: JAX found platform {device['platform']!r} "
              f"({device['kind']})", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import analyze
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    compiles = CompileLog(jax)
    mix = cell.mix
    work = tempfile.mkdtemp(prefix="bench.")
    try:
        steps = {"start_s": time.perf_counter() - T_START}
        fleet = write_fleet(FleetShape.from_config(cell.config), args.seed,
                            os.path.join(work, "fleet"))
        steps["fleet_s"] = time.perf_counter() - T_START - sum(steps.values())
        warm = analyze_once(analyze, analyze_argv(
            fleet.paths, os.path.join(work, "warm"), mix, args.device_interpret))
        shutil.rmtree(os.path.join(work, "warm"))
        steps["warm_s"] = time.perf_counter() - T_START - sum(steps.values())
        classes = (warm_columns(warm["contexts"], fleet.widths, mix["workers"])
                   if mix["compute"] == "device" else [])
        steps["columns_s"] = time.perf_counter() - T_START - sum(steps.values())
        run = Run(cell, device, fleet, setup_s=time.perf_counter() - T_START)
        say("setup", seconds=run.setup_s, **steps, profiles=len(fleet.paths),
            contexts=warm["contexts"], column_classes=classes,
            compiled=compiles.compiled, cache_hits=compiles.hits)
        before = compiles.requests
        with MemorySampler(devices[:cell.chips]) as memory:
            run_window(jax, analyze, run, args, work)
        say("compiles", in_window=compiles.requests - before)
        device["memory_peak_bytes"] = memory.peak
        stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
        say("memory", window_peak_bytes=memory.peak,
            process_peak_bytes=max((s.get("peak_bytes_in_use", 0)
                                    for s in stats), default=None))
        numbers = check(run)
        if args.trace == 1:
            read_trace(run)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    report(run, numbers, args.trace == 1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
