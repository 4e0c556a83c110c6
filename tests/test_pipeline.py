"""Zero-copy data plane: fused phase-2 kernel, shm slab transport, mmap
profile loads.  The central assertion everywhere: every path (fused vs
legacy pipeline, shm vs pickle transport, all four executors) produces
byte-identical databases."""
import hashlib
import os
import signal
import sys
import time

import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, st

from repro.core.aggregate import AggregationConfig, StreamingAggregator
from repro.core.cct import ContextTree
from repro.core.metrics import INCLUSIVE_BIT
from repro.core.pipeline import fused_transform
from repro.core.propagate import (propagate_inclusive,
                                  propagate_inclusive_reference,
                                  redistribute_placeholders)
from repro.core.sparse import MeasurementProfile, SparseMetrics
from repro.runtime import SlabArena, get_executor
from repro.runtime.shm import attach, sections_layout
from repro.utils import binio
from tests.conftest import make_profile


def _digest(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _save_workload(tmp_path, rng, n=8, **kw):
    paths = []
    for i in range(n):
        prof = make_profile(rng, n_nodes=70, n_metrics=6, density=0.3,
                            n_trace=10, identity={"rank": i}, **kw)
        p = tmp_path / f"prof{i:03d}.rprf"
        prof.save(p)
        paths.append(str(p))
    return paths


def _random_tree_case(rng, max_nodes=60):
    """A preorder-space tree + a random profile remapped onto it."""
    t = ContextTree()
    for _ in range(int(rng.integers(2, max_nodes))):
        t.child(int(rng.integers(0, len(t))), int(rng.integers(1, 5)),
                f"n{rng.integers(0, 8)}")
    pos, order, end = t.preorder()
    n = len(t)
    parent_pre = np.full(n, -1, np.int64)
    for c in range(1, n):
        parent_pre[pos[c]] = pos[t.parent[c]]
    n_local = int(rng.integers(1, 30))
    remap = pos[rng.integers(0, n, n_local)]
    x = int(rng.integers(0, 150))
    sm = SparseMetrics.from_triplets(
        rng.integers(0, n_local, x), rng.integers(0, 6, x),
        rng.uniform(-2, 4, x))
    routes = {}
    if rng.integers(0, 2):
        for ph in rng.choice(n, size=min(3, n), replace=False):
            k = int(rng.integers(1, 4))
            routes[int(ph)] = (rng.integers(0, n, k).astype(np.int64),
                               rng.uniform(0.1, 2.0, k))
    return sm, remap, routes, parent_pre, end, n


# ---------------------------------------------------------------------------
# fused kernel vs the legacy three-pass chain: byte-identical planes
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.booleans())
def test_fused_transform_bytes_equal_legacy_chain(seed, keep_exclusive):
    rng = np.random.default_rng(seed)
    sm, remap, routes, parent_pre, end, n = _random_tree_case(rng)
    legacy = sm.remap_contexts(remap)
    if routes:
        legacy = redistribute_placeholders(legacy, routes)
    legacy = propagate_inclusive(legacy, np.arange(n), end,
                                 keep_exclusive=keep_exclusive)
    fused = fused_transform(sm, remap, routes, parent_pre, end,
                            keep_exclusive=keep_exclusive)
    assert legacy.encode() == fused.encode()


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_fused_transform_matches_recursive_reference(seed):
    """Property test against the paper's per-node recursive walk."""
    rng = np.random.default_rng(seed)
    sm, remap, routes, parent_pre, end, n = _random_tree_case(rng)
    fused = fused_transform(sm, remap, {}, parent_pre, end)  # no routes:
    # the reference oracle models propagation only, not redistribution
    remapped = sm.remap_contexts(remap)
    ref = propagate_inclusive_reference(remapped, parent_pre)
    got = {(int(c), int(m)): v for c, m, v in zip(*fused.triplets())}
    want = {(int(c), int(m)): v for c, m, v in zip(*ref.triplets())}
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-9, abs=1e-12), k


def test_fused_sparse_and_dense_branches_identical(rng):
    """The density cutoff is a performance knob: both inclusive branches
    must emit identical bytes, or the cutoff would leak into outputs."""
    from repro.core import pipeline as pl
    sm, remap, routes, parent_pre, end, n = _random_tree_case(rng, 50)
    dense_small, frac = pl.DENSE_SMALL, pl.DENSE_FRACTION
    try:
        pl.DENSE_SMALL, pl.DENSE_FRACTION = 1 << 30, 0.0   # always dense
        a = fused_transform(sm, remap, routes, parent_pre, end)
        pl.DENSE_SMALL, pl.DENSE_FRACTION = 0, 2.0         # always sparse
        b = fused_transform(sm, remap, routes, parent_pre, end)
    finally:
        pl.DENSE_SMALL, pl.DENSE_FRACTION = dense_small, frac
    assert a.encode() == b.encode()


def test_fused_inclusive_values_simple_chain():
    """Hand-checked case: root -> a -> b chain, exclusive 1/2/4."""
    parent = np.array([-1, 0, 1])
    end = np.array([3, 3, 3])
    sm = SparseMetrics.from_triplets([0, 1, 2], [0, 0, 0], [1.0, 2.0, 4.0])
    out = fused_transform(sm, np.arange(3), {}, parent, end)
    incl = {int(c): v for c, m, v in zip(*out.triplets())
            if m & INCLUSIVE_BIT}
    assert incl == {0: 7.0, 1: 6.0, 2: 4.0}


# ---------------------------------------------------------------------------
# zero-copy loads
# ---------------------------------------------------------------------------

def test_unpack_array_returns_view_not_copy():
    arr = np.arange(32, dtype=np.float64)
    buf = b"pad!" + binio.pack_array(arr)
    out, off = binio.unpack_array(buf, 4)
    assert not out.flags.owndata          # aliases the buffer
    assert not out.flags.writeable        # bytes-backed views stay read-only
    np.testing.assert_array_equal(out, arr)
    assert off == len(buf)


def test_pack_array_into_matches_pack_array(rng):
    for arr in (np.arange(7, dtype=np.uint16), np.empty(0, np.float64),
                rng.uniform(size=(3, 4)), np.uint32(5) * np.ones((), np.uint32)):
        ref = binio.pack_array(arr)
        buf = bytearray(len(ref))
        end = binio.pack_array_into(buf, 0, arr)
        assert end == len(ref)
        assert bytes(buf) == ref


def test_profile_load_arrays_alias_the_mapping(tmp_path, rng):
    prof = make_profile(rng)
    p = tmp_path / "p.rprf"
    prof.save(p)
    loaded = MeasurementProfile.load(p)
    for arr in (loaded.metrics.val, loaded.metrics.ctx, loaded.trace.time):
        assert not arr.flags.owndata
        assert not arr.flags.writeable
    np.testing.assert_array_equal(loaded.metrics.val, prof.metrics.val)
    np.testing.assert_array_equal(loaded.trace.ctx, prof.trace.ctx)


def test_encode_into_matches_encode(rng):
    sm = SparseMetrics.from_triplets(rng.integers(0, 9, 30),
                                     rng.integers(0, 4, 30),
                                     rng.uniform(1, 2, 30))
    ref = sm.encode()
    assert sm.encoded_nbytes() == len(ref)
    buf = bytearray(len(ref))
    assert sm.encode_into(buf, 0) == len(ref)
    assert bytes(buf) == ref


# ---------------------------------------------------------------------------
# engine parity: pipelines x transports x executors, one database
# ---------------------------------------------------------------------------

def test_parity_fused_vs_legacy_all_executors(tmp_path, rng):
    paths = _save_workload(tmp_path, rng)
    digests = set()
    results = []
    for executor in ("serial", "threads", "processes", "ranks"):
        for pipeline in ("fused", "legacy"):
            cfg = AggregationConfig(executor=executor, n_workers=2,
                                    n_threads=2, pipeline=pipeline)
            res = StreamingAggregator(
                tmp_path / f"{executor}_{pipeline}", cfg).run(paths)
            results.append((executor, res))
            # ranks PMS uses per-rank segment layout (query-identical);
            # CMS + traces are byte-identical across all four
            digests.add((_digest(res.cms_path), _digest(res.trace_path),
                         res.n_contexts, res.n_values))
    assert len(digests) == 1
    stream_pms = {_digest(r.pms_path) for e, r in results if e != "ranks"}
    assert len(stream_pms) == 1


def test_parity_shm_vs_pickle_transport(tmp_path, rng):
    paths = _save_workload(tmp_path, rng)
    digests = set()
    for transport, slab in [("pickle", 1 << 20), ("shm", 1 << 20),
                            ("shm", 128)]:   # 128B forces one-shot fallback
        cfg = AggregationConfig(executor="processes", n_workers=3,
                                plane_transport=transport,
                                shm_slab_bytes=slab)
        res = StreamingAggregator(
            tmp_path / f"t_{transport}_{slab}", cfg).run(paths)
        digests.add((_digest(res.pms_path), _digest(res.cms_path),
                     _digest(res.trace_path)))
    assert len(digests) == 1


def test_parity_with_lexical_routes_fused(tmp_path):
    """Superposition routes through the fused kernel: identical across
    executors and identical to the legacy pipeline."""
    from tests.test_aggregate import _profile_with_structure
    ppath = _profile_with_structure(tmp_path, fused=True)
    digests = set()
    for executor in ("serial", "threads", "processes"):
        for pipeline in ("fused", "legacy"):
            cfg = AggregationConfig(executor=executor, n_workers=2,
                                    pipeline=pipeline)
            res = StreamingAggregator(
                tmp_path / f"lex_{executor}_{pipeline}", cfg).run([ppath])
            digests.add((_digest(res.pms_path), _digest(res.cms_path)))
    assert len(digests) == 1


def test_sharded_sink_residency_bounded_by_window(tmp_path, rng):
    """The sharded path now honors the bounded sink: out-of-order plane
    residency (and the slab arena) stay within the window instead of
    O(n_profiles)."""
    paths = _save_workload(tmp_path, rng, n=12)
    cfg = AggregationConfig(executor="processes", n_workers=3, sink_window=3)
    res = StreamingAggregator(tmp_path / "bounded", cfg).run(paths)
    assert res.timings["sink_peak"] <= 3
    base = StreamingAggregator(
        tmp_path / "base", AggregationConfig(executor="serial")).run(paths)
    assert _digest(res.pms_path) == _digest(base.pms_path)
    assert _digest(res.cms_path) == _digest(base.cms_path)


def test_sharded_unbounded_pickle_feed_still_works(tmp_path, rng):
    """sink_window=0 ('unbounded') with the pickle transport keeps the
    historical unthrottled feed — no slab scarcity, no credit gate."""
    paths = _save_workload(tmp_path, rng, n=6)
    cfg = AggregationConfig(executor="processes", n_workers=2, sink_window=0,
                            plane_transport="pickle")
    res = StreamingAggregator(tmp_path / "unb", cfg).run(paths)
    base = StreamingAggregator(
        tmp_path / "unb_base", AggregationConfig(executor="serial")).run(paths)
    assert _digest(res.pms_path) == _digest(base.pms_path)
    assert _digest(res.cms_path) == _digest(base.cms_path)


def test_unknown_pipeline_and_transport_are_value_errors(tmp_path):
    with pytest.raises(ValueError, match="pipeline"):
        StreamingAggregator(tmp_path / "a", AggregationConfig(
            pipeline="warp")).run([])
    with pytest.raises(ValueError, match="plane_transport"):
        StreamingAggregator(tmp_path / "b", AggregationConfig(
            plane_transport="carrier-pigeon")).run([])


# ---------------------------------------------------------------------------
# slab arena + worker-death liveness
# ---------------------------------------------------------------------------

def test_slab_arena_acquire_release_cycle():
    arena = SlabArena(2, 1024)
    try:
        a = arena.acquire()
        b = arena.acquire()
        assert a != b
        with pytest.raises(RuntimeError, match="exhausted"):
            arena.acquire()
        arena.release(a)
        assert arena.acquire() == a
        # worker-visible roundtrip through an attach
        arena.view(b)[:4] = b"ping"
        seg = attach(b)
        assert bytes(seg.buf[:4]) == b"ping"
        seg.close()
    finally:
        arena.close()
    arena.close()  # idempotent


def test_sections_layout_is_aligned():
    offs, total = sections_layout([13, 0, 7, 8])
    assert offs == [0, 16, 16, 24]
    assert total == 32
    assert all(o % 8 == 0 for o in offs)


def _kill_self(task):
    os.kill(os.getpid(), signal.SIGKILL)


def test_killed_worker_raises_not_hangs():
    """SIGKILL mid-task must surface as BrokenProcessPool-style failure in
    the parent, not a silent respawn + eternal hang (the mp.Pool failure
    mode this runtime replaced)."""
    ex = get_executor("processes", 2)
    t0 = time.monotonic()
    with pytest.raises(Exception):
        list(ex.map_unordered(_kill_self, [0, 1, 2]))
    assert time.monotonic() - t0 < 60


_KILL_MARKER = "prof002"


def _kill_on_marker(task):
    from repro.core.aggregate import _phase2_profile_worker
    if _KILL_MARKER in task[0]:
        os.kill(os.getpid(), signal.SIGKILL)
    return _phase2_profile_worker(task)


@pytest.mark.skipif(sys.platform != "linux", reason="fork start method")
def test_killed_worker_mid_slab_raises_and_cleans_up(tmp_path, rng,
                                                     monkeypatch):
    """A worker SIGKILLed while owning a slab: the parent must raise (not
    hang waiting on the lost plane) and unlink the whole arena."""
    import repro.core.aggregate as agg_mod
    monkeypatch.setattr(agg_mod, "_phase2_profile_worker", _kill_on_marker)
    paths = _save_workload(tmp_path, rng, n=6)
    before = {f for f in os.listdir("/dev/shm")} if os.path.isdir("/dev/shm") \
        else set()
    cfg = AggregationConfig(executor="processes", n_workers=2,
                            plane_transport="shm")
    t0 = time.monotonic()
    with pytest.raises(Exception):
        StreamingAggregator(tmp_path / "killed", cfg).run(paths)
    assert time.monotonic() - t0 < 60
    if os.path.isdir("/dev/shm"):
        leaked = {f for f in os.listdir("/dev/shm")
                  if f.startswith("psm_")} - before
        assert not leaked


def test_map_throttled_respects_credits():
    ex = get_executor("processes", 2)
    pulled = []

    def tasks():
        for i in range(6):
            pulled.append(i)
            yield i

    credit = {"n": 2}
    out = []
    for i, r in ex.map_throttled(_echo, tasks(),
                                 credits=lambda: credit["n"]):
        # at any point, no more tasks were pulled than credits granted
        assert len(pulled) <= credit["n"]
        out.append((i, r))
        credit["n"] += 1   # consuming grants another credit
    assert sorted(out) == [(i, i) for i in range(6)]


def _echo(x):
    return x


def test_map_throttled_zero_credit_stall_is_an_error():
    ex = get_executor("processes", 2)
    with pytest.raises(RuntimeError, match="stalled"):
        list(ex.map_throttled(_echo, [1, 2], credits=lambda: 0))


def test_map_throttled_discards_unyielded_results():
    """An aborting caller must not strand completed results: whatever
    finished but was never yielded goes through on_discard (the hook that
    unlinks one-shot shm segments on the sharded abort path)."""
    ex = get_executor("processes", 2)
    discarded = []
    gen = ex.map_throttled(_echo, range(4), credits=lambda: 10,
                           on_discard=discarded.append)
    first = next(gen)
    time.sleep(0.5)          # let the remaining instant tasks complete
    gen.close()              # caller aborts mid-iteration
    assert first not in discarded
    assert discarded         # the finished-but-unyielded results arrived
    assert all(isinstance(d, tuple) and d[0] == d[1] for d in discarded)


# ---------------------------------------------------------------------------
# key packing boundaries: loud errors instead of silent key corruption
# ---------------------------------------------------------------------------

def test_fused_transform_rejects_inclusive_bit_metric_ids():
    """A raw mid >= 2^15 would silently alias INCLUSIVE_BIT in the packed
    keys; the shared validation must refuse it loudly."""
    sm = SparseMetrics.from_triplets([0], [1 << 15], [1.0])
    with pytest.raises(ValueError, match="INCLUSIVE_BIT"):
        fused_transform(sm, np.zeros(1, np.int64), {}, np.array([-1]),
                        np.array([1]))


def test_fused_transform_rejects_overflowing_context_ids():
    """ctx >= 2^47 would wrap the signed int64 keys negative."""
    sm = SparseMetrics.from_triplets([0], [0], [1.0])
    huge = np.array([1 << 47], np.int64)
    with pytest.raises(ValueError, match="2\\^47"):
        fused_transform(sm, huge, {}, np.array([-1]), np.array([1]))


def test_pack_keys_boundaries():
    from repro.core.stats import pack_keys
    # the packed form admits the inclusive bit but not a 17-bit mid
    pack_keys(np.array([5]), np.array([3 | INCLUSIVE_BIT]))
    with pytest.raises(ValueError, match="16 bits"):
        pack_keys(np.array([5]), np.array([1 << 16]))
    with pytest.raises(ValueError, match="2\\^47"):
        pack_keys(np.array([1 << 47]), np.array([0]))


# ---------------------------------------------------------------------------
# device compute: the Pallas-kernel phase-2 backend
# ---------------------------------------------------------------------------

def _device_aggregator(end, **kw):
    from repro.kernels.batch import DeviceAggregator
    return DeviceAggregator(np.asarray(end, np.int64), **kw)


def _compare_planes_tolerant(cpu, dev, atol=1e-3, rtol=1e-4):
    """f32-class planes: device values carry f32 rounding, and near-zero
    inclusive sums may round to exactly 0.0 and drop from the sparse plane.
    Keys missing on one side must be tiny; common keys must agree to f32
    precision."""
    got = {(int(c), int(m)): v for c, m, v in zip(*dev.triplets())}
    want = {(int(c), int(m)): v for c, m, v in zip(*cpu.triplets())}
    for k in set(got) ^ set(want):
        v = got.get(k, want.get(k))
        assert abs(v) < atol, (k, v)
    for k in set(got) & set(want):
        assert got[k] == pytest.approx(want[k], rel=rtol, abs=atol), k


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_fused_device_matches_cpu_tolerantly(seed):
    """Property: the device path agrees with the fused CPU plane to f32
    precision on arbitrary (f32-class) planes, routes included."""
    rng = np.random.default_rng(seed)
    sm, remap, routes, parent_pre, end, n = _random_tree_case(rng)
    cpu = fused_transform(sm, remap, routes, parent_pre, end)
    dev = _device_aggregator(end, offload_combine=True, combine_min=1)
    out = fused_transform(sm, remap, routes, parent_pre, end, device=dev)
    _compare_planes_tolerant(cpu, out)


def test_device_inclusive_adds_no_stray_keys():
    """f32 prefix sums taken in different orders can leave a last-bit
    residue where a subtree is empty; the device plane must not turn that
    into keys the CPU plane lacks (its keys are a subset, values close)."""
    rng = np.random.default_rng(7)
    t = ContextTree()  # root -> 20 modules -> 300 leaves each
    for mod in [t.child(0, 1, f"m{i}") for i in range(20)]:
        for _ in range(300):
            t.child(mod, 2, f"n{len(t)}")
    n = len(t)
    pos, order, end = t.preorder()
    parent_pre = np.full(n, -1, np.int64)
    for c in range(1, n):
        parent_pre[pos[c]] = pos[t.parent[c]]
    x = 500
    sm = SparseMetrics.from_triplets(rng.integers(0, 2000, x),
                                     rng.integers(0, 4, x),
                                     rng.exponential(1.0, x))
    cpu = fused_transform(sm, pos, {}, parent_pre, end)
    out = fused_transform(sm, pos, {}, parent_pre, end,
                          device=_device_aggregator(end))
    want = set(zip(*(a.tolist() for a in cpu.triplets()[:2])))
    got = set(zip(*(a.tolist() for a in out.triplets()[:2])))
    assert got <= want and len(got) >= len(want) - 5
    _compare_planes_tolerant(cpu, out)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_fused_device_bytes_equal_cpu_on_exact_planes(seed):
    """Integer values within the 2^24 f32-exactness budget: the device
    plane must be byte-identical to the CPU plane (the "exact" class of the
    repro.kernels.batch dtype contract)."""
    rng = np.random.default_rng(seed)
    sm, remap, routes, parent_pre, end, n = _random_tree_case(rng)
    r, m, _ = sm.triplets()
    if r.size == 0:
        return
    sm = SparseMetrics.from_triplets(r, m, rng.integers(1, 8, r.size)
                                     .astype(np.float64))
    cpu = fused_transform(sm, remap, {}, parent_pre, end)
    dev = _device_aggregator(end, offload_combine=True, combine_min=1)
    out = fused_transform(sm, remap, {}, parent_pre, end, device=dev)
    assert cpu.encode() == out.encode()


def _inclusive_device_dense(ectx, evals, col, m, prof_mids, parent, device):
    """The device propagation as it was before triplets, the oracle of the
    test below: the (n, m) f32 matrix built on the host, the dense launch,
    and the gather at the subtree support on the host."""
    from repro.core import pipeline
    dense = np.zeros((device.n, m), dtype=np.float32)
    dense[ectx, col] = evals
    incl = device.inclusive(dense)
    ir, ic = pipeline._subtree_support(ectx, col, m, parent)
    ivals = incl[ir, ic]
    nz = ivals != 0.0
    ir, ic = ir[nz], ic[nz]
    ikeys = ir * (1 << pipeline._KEY_SHIFT) + (prof_mids[ic] | INCLUSIVE_BIT)
    return ikeys, ivals[nz].astype(np.float64)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_fused_device_bytes_equal_dense_launch_on_f32_planes(seed):
    """f32-class planes, routes included: shipping the triplets and reading
    back the support gives the plane the dense launch gave, byte for byte
    (the same matrix, scanned at the same shape, read at the same pairs)."""
    from repro.core import pipeline
    from repro.kernels.batch import classify_plane
    rng = np.random.default_rng(seed)
    sm, remap, routes, parent_pre, end, n = _random_tree_case(rng)
    assert sm.n_values == 0 or classify_plane(sm.triplets()[2]) == "f32"
    dev = _device_aggregator(end)
    out = fused_transform(sm, remap, routes, parent_pre, end, device=dev)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pipeline, "_inclusive_device", _inclusive_device_dense)
        want = fused_transform(sm, remap, routes, parent_pre, end, device=dev)
    assert out.encode() == want.encode()


def test_device_path_edge_cases(rng):
    """Empty profile, single metric, and all-placeholder planes must all
    survive the device dispatch."""
    parent = np.array([-1, 0, 0], np.int64)
    end = np.array([3, 2, 3], np.int64)
    dev = _device_aggregator(end, offload_combine=True, combine_min=1)

    empty = SparseMetrics.from_triplets([], [], [])
    out = fused_transform(empty, np.arange(3), {}, parent, end, device=dev)
    assert out.n_values == 0

    single = SparseMetrics.from_triplets([1], [0], [2.0])
    out = fused_transform(single, np.arange(3), {}, parent, end, device=dev)
    ref = fused_transform(single, np.arange(3), {}, parent, end)
    assert out.encode() == ref.encode()

    # every entry sits on a placeholder that routes to leaves 1 and 2
    ph = SparseMetrics.from_triplets([0, 0], [0, 0], [1.0, 3.0])
    routes = {0: (np.array([1, 2], np.int64), np.array([1.0, 1.0]))}
    out = fused_transform(ph, np.arange(3), routes, parent, end, device=dev)
    ref = fused_transform(ph, np.arange(3), routes, parent, end)
    assert out.encode() == ref.encode()


def _save_int_workload(tmp_path, rng, n=6):
    """Integer-valued profiles: every plane classifies "exact", so the
    device path must be byte-identical to CPU end to end."""
    from tests.conftest import random_tree
    from repro.core.sparse import Trace
    paths = []
    for i in range(n):
        tree = random_tree(rng, 60)
        nn = len(tree.parent)
        x = max(int(nn * 6 * 0.3), 1)
        sm = SparseMetrics.from_triplets(
            rng.integers(0, nn, x), rng.integers(0, 6, x),
            rng.integers(1, 9, x).astype(np.float64))
        trace = Trace(np.sort(rng.uniform(0, 1, 10)),
                      rng.integers(0, nn, 10).astype(np.uint32))
        prof = MeasurementProfile(
            environment={"app": "test", "metrics": 6},
            identity={"rank": i}, file_paths=["bin/test"],
            tree=tree, trace=trace, metrics=sm)
        p = tmp_path / f"prof{i:03d}.rprf"
        prof.save(p)
        paths.append(str(p))
    return paths


def test_device_executor_parity_byte_identical(tmp_path, rng):
    """serial/threads with compute="device" (interpret mode) on an
    exact-class workload: all digests equal each other AND the cpu run's."""
    paths = _save_int_workload(tmp_path, rng)
    digests = set()
    for executor, workers in [("serial", 1), ("threads", 3)]:
        cfg = AggregationConfig(executor=executor, n_workers=workers,
                                compute="device", device_interpret=True)
        res = StreamingAggregator(
            tmp_path / f"dev_{executor}", cfg).run(paths)
        digests.add((_digest(res.pms_path), _digest(res.cms_path)))
    cpu = StreamingAggregator(
        tmp_path / "dev_cpu_base",
        AggregationConfig(executor="serial")).run(paths)
    digests.add((_digest(cpu.pms_path), _digest(cpu.cms_path)))
    assert len(digests) == 1


def test_device_compute_falls_back_to_cpu_without_accelerator():
    """compute="device" without device_interpret on an accelerator-less
    host does not fall back: it refuses, naming the platform JAX found."""
    from repro.kernels import batch
    if batch.has_accelerator():
        pytest.skip("host has a real accelerator; the refusal is unreachable")
    with pytest.raises(RuntimeError, match="no accelerator.*'cpu'"):
        AggregationConfig(executor="threads", n_workers=2, compute="device")
    cfg = AggregationConfig(executor="threads", n_workers=2,
                            compute="device", device_interpret=True)
    assert cfg.compute == "device"


def test_device_requires_fused_pipeline(tmp_path):
    with pytest.raises(ValueError, match="fused"):
        StreamingAggregator(tmp_path / "x", AggregationConfig(
            compute="device", pipeline="legacy")).run([])
    with pytest.raises(ValueError, match="compute"):
        StreamingAggregator(tmp_path / "y", AggregationConfig(
            compute="quantum")).run([])


@pytest.mark.parametrize("executor", ["processes", "ranks"])
def test_killed_worker_mid_device_batch_raises_and_cleans_up(
        tmp_path, rng, executor):
    """One process holds the accelerator, so compute="device" refuses the
    executors that start worker processes before any worker exists — no
    child that would open the device, no unannounced CPU run."""
    import multiprocessing
    paths = _save_int_workload(tmp_path, rng, n=2)
    with pytest.raises(ValueError, match="one process holds the accelerator"):
        StreamingAggregator(tmp_path / "dev_refused", AggregationConfig(
            executor=executor, n_workers=2, compute="device",
            device_interpret=True)).run(paths)
    assert not multiprocessing.active_children()
    assert not (tmp_path / "dev_refused").exists()


def test_compile_cache_dir_is_env_or_fixed_checkout_path(monkeypatch):
    """The persistent compile cache lives where JAX_COMPILATION_CACHE_DIR
    says, else at one fixed path inside the checkout (the path is part of
    the cache key, so it must not move between runs)."""
    from pathlib import Path

    from repro.utils import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax-cache")
    assert compile_cache.compile_cache_dir() == "/elsewhere/jax-cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = Path(__file__).resolve().parents[1]
    assert compile_cache.compile_cache_dir() == str(repo / ".jax_cache")
    assert compile_cache.compile_cache_dir() == compile_cache.compile_cache_dir()


def test_cms_device_compute_byte_identical(tmp_path, rng):
    """CMS offsets through the int32 exclusive_scan kernel (and, on real
    accelerators, the census histogram): integer ops, so the CMS file must
    be byte-identical to the numpy build."""
    from repro.core import cms as cms_mod
    paths = _save_workload(tmp_path, rng, n=5)
    res = StreamingAggregator(
        tmp_path / "cms_base", AggregationConfig(executor="serial")).run(paths)
    out_cpu = tmp_path / "cpu.cms"
    out_dev = tmp_path / "dev.cms"
    cms_mod.build_cms(res.pms_path, out_cpu, compute="cpu")
    cms_mod.build_cms(res.pms_path, out_dev, compute="device")
    assert _digest(out_cpu) == _digest(out_dev)
    assert _digest(out_cpu) == _digest(res.cms_path)
