"""The analysis timer's spans and counters: self times across threads,
how much of phase 2 the leaf spans cover, the device transfer counters
against the launches that were made, and the spans on a profiler trace's
clock."""
import contextlib
import glob
import hashlib
import threading

import numpy as np
import pytest

from repro.core import timer as timer_mod
from repro.core.aggregate import AggregationConfig, StreamingAggregator
from repro.core.cms import census
from repro.core.cct import (KIND_LINE, KIND_LOOP, KIND_MODULE, KIND_OP,
                            KIND_PHASE, KIND_ROUTE, ContextTree)
from repro.core.lexical import StructureInfo
from repro.core.metrics import INCLUSIVE_BIT
from repro.core.pms import PMSReader
from repro.core.sparse import MeasurementProfile, SparseMetrics
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


# ---------------------------------------------------------------------------
# the route split (paper §4.1.3): its span and counters, on a fleet whose
# binaries carry structure
# ---------------------------------------------------------------------------

def _structured_fleet(tmp_path, rng, *, exact=False, n=6, n_ops=40,
                      scopes=2, routed_share=0.3, n_routes=3):
    """A tiny PeleC(1+82)-shaped fleet with binary structure: CPU rank
    profiles (metric 0) and GPU stream profiles (metrics 1-82) alternate.
    Each binary's structure file names every op under ``scopes`` scopes;
    ``routed_share`` of the GPU binary's ops have ``n_routes`` weighted
    routes.  Each profile holds a random subset of the ops, with a line
    below some of them, and values on ops and lines.  With ``exact``,
    values are small integers and a routed op's weights are a permutation
    of (1, 1, 2), so every split value and every sum is exact in float32.

    Returns the paths and, per profile, ``(placeholders, entries)``: its
    routed ops, and its distinct (routed op, metric) entries."""
    ops = [f"op{i}" for i in range(n_ops)]
    files, routed = {}, set()
    for binary in ("cpu", "gpu"):
        s = StructureInfo(binary)
        if binary == "gpu":
            routed = set(rng.choice(n_ops, int(n_ops * routed_share),
                                    replace=False).tolist())
        for i, op in enumerate(ops):
            k = n_routes if binary == "gpu" and i in routed else 1
            weights = (rng.permutation([1.0, 1.0, 2.0]) if exact and k == 3
                       else rng.integers(1, 1001, k).astype(float))
            for r in range(k):
                path = [((KIND_MODULE, KIND_LOOP)[d % 2],
                         f"{binary}.{'fl'[d % 2]}{(i + r + d) % 7}.r{r}")
                        for d in range(scopes)]
                s.add_op(op, path, weights[r])
        files[binary] = str(tmp_path / f"{binary}.struct.json")
        s.save(files[binary])

    paths, expected = [], []
    for p in range(n):
        gpu = p % 2 == 1
        t = ContextTree()
        main = t.child(0, KIND_PHASE, "main")
        mods = [t.child(main, KIND_MODULE, f"mod{j}") for j in range(4)]
        held = sorted(rng.choice(n_ops, n_ops * 3 // 5, replace=False))
        rows, mids = [], []
        entries = 0
        for i in held:
            op = t.child(mods[i % 4], KIND_OP, ops[i])
            line = t.child(op, KIND_LINE, f"line{i}") if i % 3 == 0 else None
            pool = np.arange(1, 83) if gpu else np.array([0])
            m = rng.choice(pool, min(2, pool.size), replace=False)
            rows += [op] * m.size
            mids += m.tolist()
            if gpu and i in routed:
                entries += m.size
            if line is not None:
                rows.append(line)
                mids.append(int(m[0]))
        vals = (rng.integers(1, 9, len(rows)).astype(float) if exact
                else rng.exponential(1.0, len(rows)))
        prof = MeasurementProfile(
            identity={"rank": p // 2, "kind": "gpu" if gpu else "cpu"},
            file_paths=[files["gpu" if gpu else "cpu"]], tree=t,
            metrics=SparseMetrics.from_triplets(rows, mids, vals))
        path = tmp_path / f"prof{p:03d}.rprf"
        prof.save(path)
        paths.append(str(path))
        expected.append((sum(i in routed for i in held) if gpu else 0,
                         entries))
    return paths, expected


def _config(compute, **kw):
    return AggregationConfig(compute=compute,
                             device_interpret=compute == "device", **kw)


@pytest.mark.parametrize("compute", ["cpu", "device"])
def test_route_counters_count_the_split(tmp_path, rng, compute):
    """``route_placeholders`` counts each profile's placeholders; each
    placeholder entry, combined, goes to every leaf of its op's routes."""
    paths, expected = _structured_fleet(tmp_path, rng)
    res = StreamingAggregator(tmp_path / "db", _config(
        compute, executor="threads", n_workers=3)).run(paths)
    t = res.timings
    placeholders = sum(ph for ph, _ in expected)
    entries = sum(e for _, e in expected)
    assert placeholders > 0 and entries > 0
    assert t["route_placeholders"] == placeholders
    assert t["route_values_in"] == entries
    assert t["route_values_out"] == 3 * entries   # three routes an op
    assert t["phase2/routes"] > 0
    # every placeholder of the unified tree is some profile's
    with PMSReader(res.pms_path) as pms:
        kinds = np.asarray(pms.tree.kind)
    assert 0 < np.count_nonzero(kinds == KIND_ROUTE) <= placeholders


@pytest.mark.parametrize("compute", ["cpu", "device"])
def test_no_route_span_or_counts_without_structure(tmp_path, rng, compute):
    paths = _fleet(tmp_path, rng, n=4, n_nodes=120)
    t = StreamingAggregator(tmp_path / "db", _config(compute)).run(
        paths).timings
    assert "phase2/routes" not in t
    assert t.get("route_values_in", 0) == t.get("route_values_out", 0) == 0
    assert t.get("route_placeholders", 0) == 0


def _db_bytes(res):
    with open(res.pms_path, "rb") as a, open(res.cms_path, "rb") as b:
        return a.read(), b.read()


@pytest.mark.parametrize("compute", ["cpu", "device"])
def test_route_span_leaves_the_database_as_it_was(tmp_path, rng, compute,
                                                  monkeypatch):
    """The databases of a structured fleet are the same bytes with every
    span of the timer stubbed out."""
    paths, _ = _structured_fleet(tmp_path, rng)
    cfg = _config(compute, executor="threads", n_workers=3)
    timed = _db_bytes(StreamingAggregator(tmp_path / "timed", cfg).run(paths))

    @contextlib.contextmanager
    def no_span(self, name, *, wall=False):
        yield

    monkeypatch.setattr(PhaseTimer, "span", no_span)
    res = StreamingAggregator(tmp_path / "bare", cfg).run(paths)
    assert "phase2/routes" not in res.timings
    assert _db_bytes(res) == timed


def test_structured_fleet_device_bytes_equal_cpu(tmp_path, rng):
    """Integer values and dyadic route weights keep every split value and
    sum exact in float32: the device path (interpret mode) writes the CPU
    path's bytes, and both the legacy chain's (remap, then
    ``redistribute_placeholders``, then ``propagate_inclusive``)."""
    paths, _ = _structured_fleet(tmp_path, rng, exact=True)
    dbs = {name: _db_bytes(StreamingAggregator(tmp_path / name, cfg).run(
        paths)) for name, cfg in [
            ("device", _config("device", executor="threads", n_workers=3)),
            ("cpu", _config("cpu", executor="threads", n_workers=3)),
            ("legacy", _config("cpu", pipeline="legacy"))]}
    assert dbs["device"] == dbs["cpu"] == dbs["legacy"]


@pytest.mark.parametrize("executor", ["serial", "threads", "processes"])
def test_cms_digest_equals_the_heap_merge(tmp_path, rng, executor):
    """On a structured fleet, every executor's vectorised CMS is the heap
    merge's bytes, and ``cms_planes`` counts each context with data."""
    paths, _ = _structured_fleet(tmp_path, rng)
    runs = {strategy: StreamingAggregator(tmp_path / strategy, _config(
        "cpu", executor=executor, n_workers=3, cms_workers=3,
        group_target_bytes=2048, cms_strategy=strategy)).run(paths)
        for strategy in ("vectorized", "heap")}
    digests = {strategy: hashlib.sha256(open(res.cms_path, "rb").read()
                                        ).hexdigest()
               for strategy, res in runs.items()}
    assert digests["vectorized"] == digests["heap"]
    res = runs["vectorized"]
    with PMSReader(res.pms_path) as pms:
        x_c, _ = census(pms, res.n_contexts)
    assert res.timings["cms_planes"] == np.count_nonzero(x_c) > 0


def _planes(res):
    with PMSReader(res.pms_path) as pms:
        return [pms.plane(p) for p in range(pms.n_profiles)]


@pytest.mark.parametrize("seed", [3, 4])
def test_structured_fleet_agrees_with_the_legacy_chain(tmp_path, seed):
    """Seeded exponential values: the fused CPU path writes the legacy
    chain's bytes, and the device path (interpret mode) its values to
    float32 precision."""
    rng = np.random.default_rng(seed)
    paths, _ = _structured_fleet(tmp_path, rng)
    runs = {name: StreamingAggregator(tmp_path / name, cfg).run(paths)
            for name, cfg in [("legacy", _config("cpu", pipeline="legacy")),
                              ("cpu", _config("cpu")),
                              ("device", _config("device"))]}
    assert _db_bytes(runs["cpu"]) == _db_bytes(runs["legacy"])
    for want, got in zip(_planes(runs["legacy"]), _planes(runs["device"])):
        w = {(c, m): v for c, m, v in zip(*(a.tolist()
                                            for a in want.triplets()))}
        g = {(c, m): v for c, m, v in zip(*(a.tolist()
                                            for a in got.triplets()))}
        assert set(g) <= set(w)
        for k, v in w.items():
            assert g.get(k, 0.0) == pytest.approx(v, rel=1e-5, abs=1e-5), k
