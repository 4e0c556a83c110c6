"""The readers of the program's spans and counters: on a CPU rehearsal
(kernels in interpret mode), on a run of a program that has none of them,
and the idle attribution on hand-made planes."""
import json
from types import SimpleNamespace as NS

import pytest

from bench import run as bench_run
from bench import spans

TIMINGS = ("densify_s", "transfer_s", "transfer_MB", "launch_wait_s",
           "cms_census_s", "cms_gather_s")


def test_rehearsal_reports_every_timings_metric(benchmark_file, capsys,
                                                monkeypatch):
    # the run points JAX's cache at the checkout; keep that out of this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(bench_run.ROOT / ".jax_cache"))
    rc = bench_run.main(["--workload", "tiny-pelec.analyze", "--seed",
                         str(2**33 + 77), "--seconds", "1", "--trace", "1",
                         "--benchmark", str(benchmark_file),
                         "--device-interpret"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    for name in TIMINGS:
        assert out["metrics"][name]["value"] > 0, name
    # the CPU backend has no device plane: the idle share reads nothing
    assert "device_idle_share" not in out["metrics"]


def run_of(*timings):
    return NS(done=[{"summary": {"timings": t}} for t in timings])


@pytest.mark.parametrize("name", TIMINGS)
def test_a_program_without_the_spans_reads_nothing(name):
    run = run_of({"phase1": 1.0, "phase2": 2.0, "cms": 0.5})
    assert bench_run.load_reader(name)(run) is None


def test_sums_and_means_over_the_analyses():
    run = run_of({"device/h2d": 1.0, "device/d2h": 2.0,
                  "device_h2d_bytes": 3e6, "device_d2h_bytes": 1e6},
                 {"device/h2d": 3.0, "device_h2d_bytes": 5e6},
                 {"phase2": 1.0})
    assert bench_run.load_reader("transfer_s")(run) == pytest.approx(3.0)
    assert bench_run.load_reader("transfer_MB")(run) == pytest.approx(4.5)


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def planes(*threads):
    host = NS(name="/host:CPU", lines=[
        NS(name="python3", events=[ev("bench.analysis", 0, 1000)])] + [
        NS(name=f"worker{k}", events=[ev(*e) for e in evs])
        for k, evs in enumerate(threads)])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("%a = f32[8] add(...)", 0, 400),
                                   ev("%b = f32[8] add(...)", 800, 200)])])
    return [host, device]


def test_idle_interval_half_covered_reads_half():
    # idle [400, 800); one span covers [400, 600) of it
    r = spans.idle_attribution(planes([("phase2/densify", 300, 300)]))
    assert r["idle_s"] == pytest.approx(400e-9)
    assert r["share"] == pytest.approx(0.5)
    assert r["by_span"] == [["phase2/densify", pytest.approx(200e-9)]]


def test_innermost_spans_share_the_idle_time():
    r = spans.idle_attribution(planes(
        [("phase2/device", 400, 400), ("device/wait", 500, 300),
         ("phase2", 0, 1000)],
        [("cms/gather", 600, 100)]))
    assert r["share"] == pytest.approx(1.0)
    by = dict(r["by_span"])
    # [400, 500) phase2/device alone; [500, 600) and [700, 800) device/wait
    # alone; [600, 700) device/wait and cms/gather half each
    assert by == pytest.approx({"phase2/device": 100e-9,
                                "device/wait": 250e-9, "cms/gather": 50e-9})


def test_no_leaf_span_or_no_device_reads_nothing():
    host, device = planes([("phase2", 0, 1000)])
    assert spans.idle_attribution([host, device]) is None
    host, device = planes([("phase2/load", 0, 1000)])
    assert spans.idle_attribution([host]) is None
