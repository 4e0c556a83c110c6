"""The trace reduction: on hand-made planes, and on a trace recorded on one
v5e chip (``bench/testdata/analysis.xplane.pb``, a 4-profile PeleC-shaped
fleet through ``analyze --compute device``)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "analysis.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.analysis", 1000, 10000)])])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_a(12)", 2000, 3000),
                                       ev("jit_b(7)", 8000, 1000),
                                       ev("jit_a(12)", 500, 1000)]),
        NS(name="XLA Ops", events=[ev("%x.1 = f32[8] fusion(...)", 2000, 1000),
                                   ev("%y = f32[8] copy(...)", 2500, 2500),
                                   ev("%z = f32[8] custom-call(...)", 8000, 1000),
                                   ev("%w = f32[8] add(...)", 500, 1000)])])
    return [host, device]


def test_hand_made_planes():
    r = trace_reduce.reduce_planes(planes(), [("phase1", 0, 0.000004),
                                              ("phase2", 0.000004, 1)])
    assert r["window_s"] == pytest.approx(10e-6)
    # ops clipped to the span [1000, 11000): 500 + 3000 + 1000 ns busy
    assert r["busy_s"] == pytest.approx(4.5e-6)
    assert r["idle_share"] == pytest.approx(0.55)
    assert r["module_s"] == pytest.approx({"jit_a": 3.5e-6, "jit_b": 1e-6})
    assert r["top_ops"][0] == ["jit_a/y", pytest.approx(2.5e-6)]
    names = [g[0] for g in r["idle_gaps"]]
    assert names == ["phase2+0.000s", "phase2+0.000s", "phase1+0.000s"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([3e-6, 2e-6, 0.5e-6])


def test_no_span_or_no_device_reduces_to_nothing():
    host, device = planes()
    assert trace_reduce.reduce_planes([device]) is None
    assert trace_reduce.reduce_planes([host]) is None


def test_recorded_chip_trace():
    r = trace_reduce.reduce_file(str(TRACE), [("phase1", 0, 1.0),
                                              ("phase2", 1.0, 2.0),
                                              ("completion", 2.0, 60.0)])
    assert r is not None
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    for module in ("jit_inclusive_from_exclusive", "jit_segstats",
                   "jit_scatter_add"):
        assert r["module_s"][module] > 0
    assert 0 < len(r["top_ops"]) <= 10 and 0 < len(r["idle_gaps"]) <= 10
    assert all("/" in name and sec > 0 for name, sec in r["top_ops"])
    assert trace_reduce.module_seconds(r, ("jit_segstats",)) == \
        r["module_s"]["jit_segstats"]
