"""The analysis timer's spans and counters: self times across threads,
how much of phase 2 the leaf spans cover, the device transfer counters
against the launches that were made, and the spans on a profiler trace's
clock."""
import glob
import threading

import numpy as np
import pytest

from repro.core import timer as timer_mod
from repro.core.aggregate import AggregationConfig, StreamingAggregator
from repro.core.metrics import INCLUSIVE_BIT
from repro.core.pms import PMSReader
from repro.core.timer import PhaseTimer
from tests.conftest import make_profile


class FakeClock:
    """``time.perf_counter`` with one hand-advanced clock per thread."""

    def __init__(self):
        self._local = threading.local()

    def perf_counter(self) -> float:
        return getattr(self._local, "t", 0.0)

    def tick(self, seconds: float) -> None:
        self._local.t = self.perf_counter() + seconds


def test_self_time_of_nested_spans_on_four_threads(monkeypatch):
    clock = FakeClock()
    monkeypatch.setattr(timer_mod, "time", clock)
    timer = PhaseTimer()
    start = threading.Barrier(4)

    def work():
        start.wait()
        with timer.span("outer"):
            clock.tick(1)
            with timer.span("inner"):
                clock.tick(2)
                with timer.span("leaf"):
                    clock.tick(4)
            clock.tick(8)
            with timer.span("phase", wall=True):
                clock.tick(16)
                with timer.span("child"):
                    clock.tick(32)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # self times, summed over the four threads; a wall span keeps its whole
    # duration and still does not count against its parent's self time
    assert timer.acc == {"outer": 4 * 9, "inner": 4 * 2, "leaf": 4 * 4,
                         "phase": 4 * 48, "child": 4 * 32}


def test_span_closes_on_an_exception(monkeypatch):
    clock = FakeClock()
    monkeypatch.setattr(timer_mod, "time", clock)
    timer = PhaseTimer()
    with timer.span("outer"):
        with pytest.raises(ValueError):
            with timer.span("failing"):
                clock.tick(3)
                raise ValueError
        clock.tick(1)
    assert timer.acc == {"failing": 3, "outer": 1}


def _fleet(tmp_path, rng, n=12, n_nodes=300):
    paths = []
    for i in range(n):
        prof = make_profile(rng, n_nodes=n_nodes, n_metrics=8, density=0.3,
                            n_trace=10, identity={"rank": i})
        p = tmp_path / f"prof{i:03d}.rprf"
        prof.save(p)
        paths.append(str(p))
    return paths


def _leaf_seconds(timings, prefixes=("phase2/", "device/")) -> float:
    return sum(v for k, v in timings.items() if k.startswith(prefixes))


@pytest.mark.parametrize("compute", ["cpu", "device"])
def test_phase2_leaf_spans_cover_its_wall(tmp_path, rng, compute):
    """Serial phase 2 runs on the thread that times it, so its leaf spans'
    self times add up to nearly all of its wall time: little of it goes
    unnamed."""
    paths = _fleet(tmp_path, rng)
    cfg = AggregationConfig(executor="serial", compute=compute,
                            device_interpret=compute == "device")
    shares = []
    for rep in range(2):   # the second run is warm
        t = StreamingAggregator(tmp_path / f"db{rep}", cfg).run(paths).timings
        shares.append(_leaf_seconds(t) / t["phase2"])
    assert 0.90 <= shares[-1] <= 1.0, shares
    assert ("phase2/densify" in t) == ("device/kernel" in t) == \
        (compute == "device")


def test_transfer_counters_equal_the_launched_bytes(tmp_path, rng,
                                                    monkeypatch):
    """Every propagation launch ships its padded (row, column, value)
    triplets and support pairs, and reads one float32 back a pair; besides
    them, the tree's int32 ``end`` array goes to the device once and the
    CMS offset scan ships its sizes and reads its offsets back (the census
    and the combine stay on the host under the interpret proxy).  The value
    counters count the triplets and the pairs before padding."""
    from repro.kernels.batch import DeviceAggregator

    launched = []
    init = DeviceAggregator.__init__

    def spy_init(self, *a, **kw):
        init(self, *a, **kw)
        fn = self._incl_fn

        def spy(exclusive, end, at=None, columns=None):
            launched.append((exclusive, at, columns))
            return fn(exclusive, end, at, columns=columns)
        self._incl_fn = spy

    monkeypatch.setattr(DeviceAggregator, "__init__", spy_init)
    paths = _fleet(tmp_path, rng, n=6, n_nodes=120)
    cfg = AggregationConfig(executor="threads", n_workers=3, compute="device",
                            device_interpret=True)
    res = StreamingAggregator(tmp_path / "db", cfg).run(paths)
    t, n = res.timings, res.n_contexts
    assert len(launched) == t["device_inclusive_launches"] > 0
    assert all(columns is not None and at is not None
               for _, at, columns in launched)   # never a dense matrix
    shipped = sum(a.nbytes for exc, at, _ in launched for a in (*exc, *at))
    assert t["device_h2d_bytes"] == shipped + 4 * n + 4 * n
    pairs = sum(at[0].size for _, at, _ in launched)
    assert t["device_d2h_bytes"] == 4 * pairs + 4 * (n + 1)
    assert t["device_padded_columns"] == sum(c for _, _, c in launched)
    assert t["device_values_in"] <= sum(exc[0].size for exc, _, _ in launched)
    assert t["device_values_out"] <= pairs
    # one column per distinct exclusive metric of each profile's plane, one
    # triplet per exclusive value, a support pair per inclusive value at least
    with PMSReader(res.pms_path) as pms:
        mids = [pms.plane(p).mid for p in range(pms.n_profiles)]
    excl = [m[m < INCLUSIVE_BIT] for m in mids]
    assert t["device_columns"] == sum(np.unique(m).size for m in excl)
    assert t["device_values_in"] == sum(m.size for m in excl)
    assert t["device_values_out"] >= sum(
        np.count_nonzero(m >= INCLUSIVE_BIT) for m in mids)


def test_spans_share_the_device_trace_clock(tmp_path, rng):
    """Under a ``jax.profiler`` session each span is an annotation on its
    thread's line: phase-2 leaf spans lie inside an annotation opened
    around the analysis, and the launch's ``device/*`` spans inside the
    ``phase2/device`` span that waits for them."""
    import jax
    from jax.profiler import ProfileData

    paths = _fleet(tmp_path, rng, n=4, n_nodes=120)
    cfg = AggregationConfig(executor="serial", compute="device",
                            device_interpret=True)
    StreamingAggregator(tmp_path / "warm", cfg).run(paths)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        with jax.profiler.TraceAnnotation("test.analysis"):
            StreamingAggregator(tmp_path / "db", cfg).run(paths)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "trace/**/*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)   # the events are views into it
    events = [e for plane in data.planes if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events]

    def intervals(name):
        return [(e.start_ns, e.start_ns + e.duration_ns) for e in events
                if e.name == name]

    (outer,) = intervals("test.analysis")
    densify = intervals("phase2/densify")
    assert len(densify) == len(paths)
    assert all(outer[0] <= a and b <= outer[1] for a, b in densify)
    device = intervals("phase2/device")
    for a, b in intervals("device/kernel"):
        assert any(p <= a and b <= q for p, q in device)
    for name in ("phase1", "phase2", "completion", "cms", "cms/gather"):
        assert intervals(name), name
