"""Executor runtime: backend parity (byte-identical DBs) & crash propagation."""
import hashlib
import threading
import time

import numpy as np
import pytest

from repro.core.aggregate import AggregationConfig, StreamingAggregator
from repro.core.pms import PMSReader
from repro.runtime import (OrderedSink, available_executors, get_executor,
                           tree_reduce)
from tests.conftest import make_profile

EXECUTORS = ("serial", "threads", "processes")


def _save_workload(tmp_path, rng, n=9):
    paths = []
    for i in range(n):
        prof = make_profile(rng, n_nodes=60, n_metrics=6, density=0.3,
                            n_trace=12, identity={"rank": i, "stream": i % 2})
        p = tmp_path / f"prof{i:03d}.rprf"
        prof.save(p)
        paths.append(str(p))
    return paths


def _digest(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


# ---------------------------------------------------------------------------
# parity: every backend must produce the same analysis, byte for byte
# ---------------------------------------------------------------------------

def test_executor_parity_byte_identical(tmp_path, rng):
    paths = _save_workload(tmp_path, rng)
    results = {}
    for ex, workers in [("serial", 1), ("threads", 3), ("processes", 3),
                        ("threads", 1), ("processes", 2)]:
        cfg = AggregationConfig(executor=ex, n_workers=workers,
                                buffer_bytes=4096)
        res = StreamingAggregator(tmp_path / f"{ex}{workers}", cfg).run(paths)
        results[(ex, workers)] = res
    base = results[("serial", 1)]
    base_pms, base_cms = _digest(base.pms_path), _digest(base.cms_path)
    base_trc = _digest(base.trace_path)
    for key, res in results.items():
        assert res.n_profiles == base.n_profiles, key
        assert res.n_contexts == base.n_contexts, key
        assert res.n_values == base.n_values, key
        assert _digest(res.pms_path) == base_pms, key
        assert _digest(res.cms_path) == base_cms, key
        assert _digest(res.trace_path) == base_trc, key
    # sanity: the database is non-trivial, not vacuously identical
    with PMSReader(base.pms_path) as r:
        assert sum(r.plane(p).n_values for p in range(base.n_profiles)) > 0
        assert len(r.tree.parent) == base.n_contexts


def test_executor_parity_with_lexical_structures(tmp_path):
    """Superposition routes survive the shard-tree merge of the processes
    backend identically to the locked in-process unification."""
    from tests.test_aggregate import _profile_with_structure
    ppath = _profile_with_structure(tmp_path, fused=True)
    digests = set()
    for ex in EXECUTORS:
        cfg = AggregationConfig(executor=ex, n_workers=2)
        res = StreamingAggregator(tmp_path / f"lex_{ex}", cfg).run([ppath])
        digests.add((_digest(res.pms_path), _digest(res.cms_path)))
    assert len(digests) == 1


# ---------------------------------------------------------------------------
# crash propagation: worker exceptions surface, nothing hangs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("executor", EXECUTORS)
def test_worker_crash_propagates(tmp_path, rng, executor):
    paths = _save_workload(tmp_path, rng, n=4)
    bad = tmp_path / "bad.rprf"
    bad.write_bytes(b"this is not a profile")
    cfg = AggregationConfig(executor=executor, n_workers=2)
    with pytest.raises(Exception, match="not a profile file"):
        StreamingAggregator(tmp_path / f"crash_{executor}",
                            cfg).run(paths + [str(bad)])


@pytest.mark.parametrize("executor", EXECUTORS)
def test_map_unordered_raises_on_task_error(executor):
    ex = get_executor(executor, 2)
    with pytest.raises(ZeroDivisionError):
        list(ex.map_unordered(_one_over, [4, 2, 0, 1]))


def _one_over(x):  # module-level: must pickle into process workers
    return 1 / x


def _boom_init():
    raise RuntimeError("init boom")


@pytest.mark.parametrize("executor", EXECUTORS)
def test_initializer_crash_propagates(executor):
    """A raising initializer must surface, not hang: CPython's Pool would
    otherwise respawn init-dying workers forever."""
    ex = get_executor(executor, 2)
    with pytest.raises(RuntimeError, match="init boom"):
        list(ex.map_unordered(_one_over, [1, 2], initializer=_boom_init))


# ---------------------------------------------------------------------------
# executor interface
# ---------------------------------------------------------------------------

def test_registry_lists_builtin_backends():
    assert set(EXECUTORS) <= set(available_executors())


def test_unknown_executor_is_a_value_error(tmp_path):
    with pytest.raises(ValueError, match="unknown executor"):
        get_executor("gpu-rdma")
    agg = StreamingAggregator(tmp_path / "never",
                              AggregationConfig(executor="typo"))
    with pytest.raises(ValueError, match="unknown executor"):
        agg.run([])


@pytest.mark.parametrize("executor", EXECUTORS)
def test_map_unordered_complete_and_initialized(executor):
    ex = get_executor(executor, 3)
    got = dict(ex.map_unordered(_one_over, [1, 2, 4, 8, 16]))
    assert got == {0: 1.0, 1: 0.5, 2: 0.25, 3: 0.125, 4: 0.0625}


def test_shards_contiguous_and_balanced():
    ex = get_executor("serial", 4)
    shards = ex.shards(10)
    assert [i for sh in shards for i in sh] == list(range(10))
    assert len(shards) == 4
    assert max(len(s) for s in shards) - min(len(s) for s in shards) <= 1
    assert get_executor("serial", 8).shards(3) == [[0], [1], [2]]
    assert get_executor("serial", 2).shards(0) == []


# ---------------------------------------------------------------------------
# OrderedSink
# ---------------------------------------------------------------------------

def test_ordered_sink_reorders_any_arrival_order(rng):
    seen = []
    sink = OrderedSink(lambda i, item: seen.append((i, item)))
    order = rng.permutation(50)
    for i in order:
        sink.put(int(i), f"item{i}")
    sink.close()
    assert seen == [(i, f"item{i}") for i in range(50)]


def test_ordered_sink_concurrent_producers():
    seen = []
    sink = OrderedSink(lambda i, item: seen.append(i))
    threads = [threading.Thread(target=sink.put, args=(i, i))
               for i in reversed(range(32))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sink.close()
    assert seen == list(range(32))


def test_ordered_sink_poisons_on_consume_error():
    def consume(i, item):
        if i == 1:
            raise RuntimeError("disk full")
    sink = OrderedSink(consume)
    sink.put(0, "a")
    with pytest.raises(RuntimeError, match="disk full"):
        sink.put(1, "b")
    with pytest.raises(RuntimeError, match="disk full"):
        sink.put(2, "c")
    with pytest.raises(RuntimeError, match="disk full"):
        sink.close()


def test_ordered_sink_close_detects_gap():
    sink = OrderedSink(lambda i, item: None)
    sink.put(0, "a")
    sink.put(2, "c")  # 1 never arrives
    with pytest.raises(RuntimeError, match="missing index 1"):
        sink.close()


# ---------------------------------------------------------------------------
# bounded out-of-order window (ROADMAP known limit)
# ---------------------------------------------------------------------------

def test_ordered_sink_window_bounds_buffering():
    """Profile 0 slowest: producers 1..n must not stack O(n) items."""
    n, window = 32, 4
    seen = []
    sink = OrderedSink(lambda i, item: seen.append(i), window=window)
    threads = [threading.Thread(target=sink.put, args=(i, i))
               for i in range(1, n)]
    for t in threads:
        t.start()
    time.sleep(0.05)          # let every unblocked producer land
    assert len(seen) == 0     # nothing drains before index 0
    assert sink.max_pending <= window
    sink.put(0, 0)            # the slow head arrives; everything drains
    for t in threads:
        t.join()
    sink.close()
    assert seen == list(range(n))
    assert sink.max_pending <= window


def test_ordered_sink_fail_unblocks_producers():
    sink = OrderedSink(lambda i, item: None, window=2)
    errors = []

    def put(i):
        try:
            sink.put(i, i)
        except RuntimeError as e:
            errors.append((i, str(e)))

    blocked = [threading.Thread(target=put, args=(i,)) for i in (5, 6)]
    for t in blocked:
        t.start()
    time.sleep(0.05)
    assert all(t.is_alive() for t in blocked)  # both wait on the window
    sink.fail(RuntimeError("producer 0 died"))
    for t in blocked:
        t.join(timeout=5)
    assert not any(t.is_alive() for t in blocked)
    assert sorted(i for i, _ in errors) == [5, 6]
    with pytest.raises(RuntimeError, match="producer 0 died"):
        sink.put(7, 7)


def test_ordered_sink_window_validation():
    with pytest.raises(ValueError, match="window"):
        OrderedSink(lambda i, item: None, window=0)


def test_bounded_window_parity_and_crash(tmp_path, rng):
    """window=1 (fully serialized appends) still yields byte-identical
    output, and a worker crash under a bounded window must not hang the
    blocked producers."""
    paths = _save_workload(tmp_path, rng, n=8)
    base = StreamingAggregator(
        tmp_path / "base", AggregationConfig(executor="serial")).run(paths)
    tight = StreamingAggregator(
        tmp_path / "tight",
        AggregationConfig(executor="threads", n_workers=4,
                          sink_window=1)).run(paths)
    assert _digest(tight.pms_path) == _digest(base.pms_path)
    assert _digest(tight.cms_path) == _digest(base.cms_path)
    bad = tmp_path / "bad.rprf"
    bad.write_bytes(b"this is not a profile")
    cfg = AggregationConfig(executor="threads", n_workers=4, sink_window=1)
    with pytest.raises(Exception, match="not a profile file"):
        StreamingAggregator(tmp_path / "crash_bounded",
                            cfg).run([str(bad)] + paths)


# ---------------------------------------------------------------------------
# the ranks whole-run driver as a registered backend
# ---------------------------------------------------------------------------

def test_ranks_backend_registered():
    assert "ranks" in available_executors()
    ex = get_executor("ranks", 2)
    assert ex.driver == "ranks" and not ex.in_process


def test_ranks_backend_runs_like_the_others(tmp_path, rng):
    """AggregationConfig(executor='ranks') must produce the same *analysis*
    as the streaming backends: identical CMS/trace bytes and identical
    counts (its PMS differs only in plane layout, per-rank segments)."""
    paths = _save_workload(tmp_path, rng, n=6)
    base = StreamingAggregator(
        tmp_path / "ser", AggregationConfig(executor="serial")).run(paths)
    res = StreamingAggregator(
        tmp_path / "rnk",
        AggregationConfig(executor="ranks", n_workers=2,
                          n_threads=2)).run(paths)
    assert res.n_profiles == base.n_profiles
    assert res.n_contexts == base.n_contexts
    assert res.n_values == base.n_values
    assert _digest(res.cms_path) == _digest(base.cms_path)
    assert _digest(res.trace_path) == _digest(base.trace_path)
    with PMSReader(res.pms_path) as a, PMSReader(base.pms_path) as b:
        for pid in range(base.n_profiles):
            np.testing.assert_allclose(a.plane(pid).val, b.plane(pid).val)


def test_streaming_reducer_preserves_index_order():
    """The carry-chain fold must behave like a left-to-right reduction: its
    shape (and so any FP op order) is a pure function of n — the property
    the stats byte-parity contract leans on."""
    from repro.runtime.reduce import StreamingReducer
    for n in (0, 1, 2, 3, 7, 16, 33):
        r = StreamingReducer(lambda a, b: a + b)
        for i in range(n):
            r.push([i])
        got = r.result()
        if n == 0:
            assert got is None
        else:
            assert got == list(range(n))


# ---------------------------------------------------------------------------
# reduction machinery stays importable from its historical home
# ---------------------------------------------------------------------------

def test_tree_reduce_shared_with_rank_reduction():
    from repro.core.reduction import tree_reduce as legacy
    assert legacy is tree_reduce
    total, rounds = tree_reduce(list(np.arange(16)), lambda a, b: a + b, 2)
    assert total == 120 and rounds == 4


# ---------------------------------------------------------------------------
# stats merge tree: the async reducer must reproduce the inline fold shape
# ---------------------------------------------------------------------------

def test_async_streaming_reducer_fold_shape_identical():
    """AsyncStreamingReducer moves merges onto a pool but must keep the
    exact carry-chain shape (operand order included) — proved here with a
    non-commutative, non-associative string merge for every n in 1..16."""
    from repro.runtime.reduce import AsyncStreamingReducer, StreamingReducer

    def merge(a, b):
        return f"({a}+{b})"

    for n in range(1, 17):
        inline = StreamingReducer(merge)
        pooled = AsyncStreamingReducer(merge, n_threads=3)
        for i in range(n):
            inline.push(str(i))
            pooled.push(str(i))
        assert pooled.result() == inline.result(), n


def test_async_streaming_reducer_empty_and_errors():
    from repro.runtime.reduce import AsyncStreamingReducer

    red = AsyncStreamingReducer(lambda a, b: a + b)
    assert red.result() is None

    def boom(a, b):
        raise RuntimeError("merge failed")

    red = AsyncStreamingReducer(boom, n_threads=2)
    red.push(1)
    red.push(2)   # schedules the failing merge
    with pytest.raises(RuntimeError, match="merge failed"):
        red.result()
    red.close()   # idempotent after result()


def test_stats_merge_modes_byte_identical(tmp_path, rng):
    """The pooled fold (more than one worker) writes the inline fold's
    bytes (one worker) — only where the merges run changes."""
    paths = _save_workload(tmp_path, rng, n=7)
    digests = set()
    for executor, workers in [("serial", 1), ("threads", 1), ("threads", 2),
                              ("processes", 2)]:
        cfg = AggregationConfig(executor=executor, n_workers=workers)
        res = StreamingAggregator(
            tmp_path / f"sm_{executor}_{workers}", cfg).run(paths)
        digests.add((_digest(res.pms_path), _digest(res.cms_path)))
    assert len(digests) == 1


def test_stats_reducer_pooled_iff_more_than_one_worker(tmp_path, rng):
    """The worker count chooses the fold: pooled merges at 4 workers, the
    consume thread's at 1, and the same database bytes either way."""
    from repro.core.aggregate import _make_stats_reducer
    from repro.runtime.reduce import AsyncStreamingReducer, StreamingReducer
    paths = _save_workload(tmp_path, rng, n=9)
    digests = set()
    for workers, kind in [(1, StreamingReducer), (4, AsyncStreamingReducer)]:
        cfg = AggregationConfig(executor="threads", n_workers=workers)
        reducer = _make_stats_reducer(cfg)
        assert type(reducer) is kind
        reducer.close()
        res = StreamingAggregator(tmp_path / f"w{workers}", cfg).run(paths)
        digests.add((_digest(res.pms_path), _digest(res.cms_path)))
    assert len(digests) == 1
