"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import ops, ref


def _sorted_ids(rng, n, s):
    return np.sort(rng.integers(0, s, n)).astype(np.int32)


# ---------------------------------------------------------------------------
# segstats
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,s", [(64, 16), (512, 128), (1500, 700), (4096, 1000),
                                 (1024, 1), (8192, 3000)])
def test_segstats_matches_ref(rng, n, s):
    ids = _sorted_ids(rng, n, s)
    vals = rng.uniform(0.1, 5.0, n).astype(np.float32)
    got = ops.segstats(jnp.asarray(ids), jnp.asarray(vals), s)
    want = ref.segstats_ref(jnp.asarray(ids), jnp.asarray(vals), s)
    # empty-segment min/max finalize to 0 in ops
    want = np.array(want)
    empty = want[:, 1] == 0
    want[empty, 2] = 0.0
    want[empty, 3] = 0.0
    assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_segstats_negative_and_empty_segments(rng):
    ids = np.array([0, 0, 5, 5, 5, 9], dtype=np.int32)
    vals = np.array([-1.0, 2.0, 3.0, -4.0, 1.0, 7.0], dtype=np.float32)
    out = np.asarray(ops.segstats(jnp.asarray(ids), jnp.asarray(vals), 10))
    assert out[0, 0] == pytest.approx(1.0)       # sum
    assert out[0, 2] == pytest.approx(-1.0)      # min
    assert out[5, 3] == pytest.approx(3.0)       # max
    assert out[5, 1] == 3                         # count
    assert np.all(out[1:5] == 0) and np.all(out[6:9] == 0)


@pytest.mark.parametrize("block_n,block_s", [(256, 128), (512, 512), (1024, 256)])
def test_segstats_block_shape_sweep(rng, block_n, block_s):
    ids = _sorted_ids(rng, 2048, 600)
    vals = rng.normal(size=2048).astype(np.float32)
    got = ops.segstats(jnp.asarray(ids), jnp.asarray(vals), 600,
                       block_n=block_n, block_s=block_s)
    base = ops.segstats(jnp.asarray(ids), jnp.asarray(vals), 600)
    assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-5, atol=1e-5)


def test_segstats_matches_stats_accumulator(rng):
    """Kernel output == the engine's StatsAccumulator on identical data."""
    from repro.core.sparse import SparseMetrics
    from repro.core.stats import StatsAccumulator, pack_keys
    sms = [SparseMetrics.from_triplets(rng.integers(0, 20, 50),
                                       rng.integers(0, 8, 50),
                                       rng.uniform(0.1, 2, 50)) for _ in range(4)]
    acc = StatsAccumulator()
    for sm in sms:
        acc.update(sm)
    fin = acc.finalize()
    # kernel path: keys = ctx*2^16 + mid compacted to dense ranks
    all_keys, all_vals = [], []
    for sm in sms:
        r, m, v = sm.triplets()
        all_keys.append(pack_keys(r, m))
        all_vals.append(v)
    keys = np.concatenate(all_keys)
    vals = np.concatenate(all_vals).astype(np.float32)
    uniq, ranks = np.unique(keys, return_inverse=True)
    order = np.argsort(ranks, kind="stable")
    out = np.asarray(ops.segstats(jnp.asarray(ranks[order].astype(np.int32)),
                                  jnp.asarray(vals[order]), uniq.size))
    assert uniq.size == len(fin["ctx"])
    assert_allclose(out[:, 0], fin["sum"], rtol=1e-5)
    assert_allclose(out[:, 1], fin["count"], rtol=1e-6)
    assert_allclose(out[:, 2], fin["min"], rtol=1e-5)
    assert_allclose(out[:, 3], fin["max"], rtol=1e-5)


# ---------------------------------------------------------------------------
# blockscan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m", [(8, 1), (1024, 4), (3000, 2), (8192, 16), (17, 3)])
def test_blockscan_matches_ref(rng, n, m):
    x = rng.normal(size=(n, m)).astype(np.float32)
    got = ops.blockscan(jnp.asarray(x))
    assert_allclose(np.asarray(got), np.asarray(ref.blockscan_ref(x)),
                    rtol=1e-4, atol=1e-4)


def test_blockscan_1d_and_exclusive(rng):
    x = rng.uniform(0, 3, 1000).astype(np.float32)
    inc = np.asarray(ops.blockscan(jnp.asarray(x)))
    assert_allclose(inc, np.cumsum(x), rtol=1e-5)
    exc = np.asarray(ops.exclusive_scan(jnp.asarray(x)))
    assert exc[0] == 0
    assert_allclose(exc[-1], x.sum(), rtol=1e-5)
    assert exc.shape[0] == 1001


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_blockscan_dtypes(rng, dtype):
    x = rng.normal(size=(512, 2)).astype(dtype)
    got = np.asarray(ops.blockscan(jnp.asarray(x)))
    assert_allclose(got, np.cumsum(x, axis=0), rtol=1e-3, atol=1e-4)


def _tree_walk_case(rng, n_nodes=64, n_metrics=4):
    """A random tree's preorder ``end``, a sparse plane on it as preorder
    (row, column, value) triplets, and the tree-walk oracle's inclusive
    values by (row, column)."""
    from repro.core.metrics import INCLUSIVE_BIT
    from repro.core.propagate import propagate_inclusive
    from tests.conftest import random_sparse, random_tree
    t = random_tree(rng, n_nodes)
    sm = random_sparse(rng, len(t), n_metrics, 0.2)
    pos, order, end = t.preorder()
    ctx, mid, val = sm.triplets()
    oracle = propagate_inclusive(sm, pos, end, keep_exclusive=False)
    incl = {}
    for k in range(oracle.n_contexts):
        c = int(oracle.ctx[k])
        for m, v in zip(*oracle.context_slice(c)):
            incl[int(pos[c]), int(m) & ~INCLUSIVE_BIT] = v
    return end, (pos[ctx], mid.astype(np.int64), val), incl


def test_inclusive_from_exclusive_matches_tree_walk(rng):
    end, (rows, cols, vals), incl = _tree_walk_case(rng)
    dense = np.zeros((end.size, 4), np.float32)
    dense[rows, cols] = vals
    got = np.asarray(ops.inclusive_from_exclusive(
        jnp.asarray(dense), jnp.asarray(end)))
    for (r, c), v in incl.items():
        assert got[r, c] == pytest.approx(v, rel=1e-4)


@pytest.mark.parametrize("pad", [0, 37])
@pytest.mark.parametrize("support", ["all", "random", "empty"])
def test_sparse_inclusive_matches_tree_walk(rng, support, pad):
    """The triplet form: the device builds the matrix from the non-zeros,
    drops padding triplets that point past the last row, and returns the
    inclusive sums at the asked pairs only."""
    n_metrics = 4
    end, (rows, cols, vals), incl = _tree_walk_case(rng, n_metrics=n_metrics)
    n = end.size
    rows = np.concatenate([rows, np.full(pad, n)])       # sentinels
    cols = np.concatenate([cols, rng.integers(0, n_metrics, pad)])
    vals = np.concatenate([vals, rng.uniform(1, 9, pad)])
    if support == "all":
        ir, ic = np.divmod(np.arange(n * n_metrics), n_metrics)
    elif support == "random":
        ir = rng.integers(0, n, 50)
        ic = rng.integers(0, n_metrics, 50)
    else:
        ir = ic = np.zeros(0, np.int64)
    i32 = jnp.int32
    got = np.asarray(ops.inclusive_from_exclusive(
        (jnp.asarray(rows, i32), jnp.asarray(cols, i32),
         jnp.asarray(vals, jnp.float32)),
        jnp.asarray(end, i32), (jnp.asarray(ir, i32), jnp.asarray(ic, i32)),
        columns=n_metrics))
    assert got.shape == ir.shape
    want = [incl.get((int(r), int(c)), 0.0) for r, c in zip(ir, ic)]
    assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# scatter_add
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,s,m", [(256, 64, 1), (1024, 300, 4), (5000, 1200, 2)])
def test_scatter_add_matches_ref(rng, n, s, m):
    ids = rng.integers(0, s, n).astype(np.int32)  # UNSORTED
    vals = rng.normal(size=(n, m)).astype(np.float32)
    got = ops.scatter_add(jnp.asarray(ids), jnp.asarray(vals), s)
    want = ref.scatter_add_ref(jnp.asarray(ids), jnp.asarray(vals), s)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_histogram(rng):
    ids = rng.integers(0, 50, 4000).astype(np.int32)
    got = np.asarray(ops.histogram(jnp.asarray(ids), 50))
    assert_allclose(got, np.bincount(ids, minlength=50).astype(np.float32))


# ---------------------------------------------------------------------------
# int8_quant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2048, 4096, 1000])
def test_int8_quant_matches_ref(rng, n):
    x = rng.normal(size=n).astype(np.float32) * 3.0
    q, s, e = ops.int8_quant(jnp.asarray(x))
    # reconstruction + error == original exactly
    block = min(2048, max(128, n))
    recon = np.asarray(ops.int8_dequant(q, s, n, block))
    assert_allclose(recon + np.asarray(e), x, rtol=1e-5, atol=1e-6)
    # quantization error bounded by scale/2 per element
    scales = np.repeat(np.asarray(s), block)[:n]
    assert np.all(np.abs(np.asarray(e)) <= scales * 0.5 + 1e-7)


def test_int8_quant_zero_block():
    x = jnp.zeros(2048, jnp.float32)
    q, s, e = ops.int8_quant(x)
    assert np.all(np.asarray(q) == 0) and np.all(np.asarray(e) == 0)


# ---------------------------------------------------------------------------
# block-size clamping: lane/sublane alignment on awkward problem sizes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("requested,n,align", [
    (128, 200, 128), (512, 8, 128), (1024, 1, 128), (128, 8191, 128),
    (8, 3, 8), (256, 17, 8), (1 << 20, 100, 128),
])
def test_clamp_block_alignment_invariants(requested, n, align):
    """The clamp must always emit a positive block that is a multiple of the
    tile alignment — `min(block, max(8, n))` shapes like 200 or 17 pass
    interpret=True but are illegal BlockSpecs on real TPUs."""
    b = ops._clamp_block(requested, n, align)
    assert b > 0 and b % align == 0
    assert b >= align                     # never below one tile
    assert b <= max(align, -(-n // align) * align) or b <= requested


def test_segstats_awkward_segment_count_stays_aligned(rng):
    """num_segments=200 used to clamp block_s to 200 (not lane-aligned);
    the rounded-up clamp must keep results correct — sentinel padding rows
    land beyond num_segments and are sliced off."""
    s = 200
    ids = _sorted_ids(rng, 1024, s)
    vals = rng.uniform(0.1, 5.0, 1024).astype(np.float32)
    got = np.asarray(ops.segstats(jnp.asarray(ids), jnp.asarray(vals), s,
                                  block_s=s))  # misaligned request
    sums = np.zeros(s)
    np.add.at(sums, ids, vals.astype(np.float64))
    assert_allclose(got[:, 0], sums, rtol=1e-4)


def test_scatter_add_small_segment_count_stays_aligned(rng):
    ids = rng.integers(0, 5, 256).astype(np.int32)
    vals = rng.normal(size=256).astype(np.float32)
    got = np.asarray(ops.scatter_add(jnp.asarray(ids), jnp.asarray(vals), 5,
                                     block_s=5))
    want = np.zeros(5)
    np.add.at(want, ids, vals.astype(np.float64))
    assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_blockscan_tiny_input_stays_aligned(rng):
    x = rng.normal(size=(3, 2)).astype(np.float32)
    got = np.asarray(ops.blockscan(jnp.asarray(x), block_n=3))
    assert_allclose(got, np.cumsum(x, axis=0), rtol=1e-5)


# ---------------------------------------------------------------------------
# int8_dequant: explicit pad target, loud mismatch errors
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 100, 1000, 2047, 2048, 2049, 5000])
def test_int8_roundtrip_non_block_multiple_lengths(rng, n):
    """quant -> dequant must reconstruct (plus error) at every length, not
    just block multiples — the old dead pad arithmetic under-padded."""
    x = rng.normal(size=n).astype(np.float32) * 2.0
    q, s, e = ops.int8_quant(jnp.asarray(x))
    assert q.shape[0] == n and e.shape[0] == n
    recon = np.asarray(ops.int8_dequant(q, s, n))
    assert recon.shape[0] == n
    assert_allclose(recon + np.asarray(e), x, rtol=1e-5, atol=1e-6)


def test_int8_dequant_rejects_mismatched_scales(rng):
    x = rng.normal(size=4096).astype(np.float32)
    q, s, _ = ops.int8_quant(jnp.asarray(x))
    with pytest.raises(ValueError, match="exceed the"):
        # half the scale blocks cannot cover all 4096 quantized values
        ops.int8_dequant(q, s[:1], 4096)


# ---------------------------------------------------------------------------
# the device aggregation batching layer (repro.kernels.batch)
# ---------------------------------------------------------------------------

from repro.kernels import batch as kb  # noqa: E402


def _chain_end(n):
    """A root->child chain tree: end[i] == n for all i."""
    return np.full(n, n, dtype=np.int64)


@pytest.mark.parametrize("vals,want", [
    ([1.0, 2.0, 3.0], "exact"),
    ([], "exact"),
    ([1.5], "f32"),
    ([float(2 ** 25)], "f32"),            # |v| sum over 2^24
    ([4096.0] * 4096, "f32"),             # sum of squares over 2^24
    ([np.inf], "f32"),
    ([-3.0, 7.0], "exact"),
])
def test_classify_plane(vals, want):
    assert kb.classify_plane(np.asarray(vals, dtype=np.float64)) == want


def test_bucket_ladder():
    assert kb._bucket(1, 8) == 8
    assert kb._bucket(8, 8) == 8
    assert kb._bucket(9, 8) == 16
    assert kb._bucket(300, 128) == 512


def test_device_aggregator_inclusive_matches_numpy(rng):
    n = 40
    end = np.sort(rng.integers(1, n + 1, n))[::-1].copy()
    end = np.maximum(end, np.arange(n) + 1)   # a valid interval family
    dev = kb.DeviceAggregator(end)
    cols = rng.integers(0, 5, (n, 3)).astype(np.float32)
    out = dev.inclusive(cols)
    ps = np.concatenate([np.zeros((1, 3)), np.cumsum(cols, axis=0)])
    want = ps[end] - ps[np.arange(n)]
    assert_allclose(out, want, rtol=1e-6)
    assert dev.launches == 1 and dev.requests == 1


def _sparse_request(rng, n, width, k):
    """Unique random (row, column) triplets of integer values over an
    (n, width) matrix, and ``k`` random pairs to read back."""
    flat = rng.choice(n * width, size=min(n * width, 3 * width), replace=False)
    rows, cols = np.divmod(flat, width)
    vals = rng.integers(1, 9, flat.size).astype(np.float32)
    ir, ic = rng.integers(0, n, k), rng.integers(0, width, k)
    return rows, cols, vals, width, ir, ic


@pytest.mark.parametrize("width", [1, 5, 9, 82])
def test_sparse_launch_equals_dense_launch_bitwise(rng, width):
    """f32-class values: the triplet launch builds on the device the
    matrix the host would build and scans it at the same shape, so its
    values at the pairs are the dense launch's, bit for bit."""
    n = 300
    end = np.sort(rng.integers(1, n + 1, n))[::-1].copy()
    end = np.maximum(end, np.arange(n) + 1)
    dev = kb.DeviceAggregator(end)
    rows, cols, _, _, ir, ic = _sparse_request(rng, n, width, 400)
    vals = rng.normal(size=rows.size).astype(np.float32)
    dense = np.zeros((n, width), np.float32)
    dense[rows, cols] = vals
    got = dev.inclusive_at(rows, cols, vals, width, ir, ic)
    want = dev.inclusive(dense)[ir, ic]
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_device_aggregator_coalesces_concurrent_requests(rng, kind):
    """Threads racing into the combining funnel must each get exactly their
    own values back.  The first launch is held until the other five
    requests wait, so those five share the second.  Sparse requests of
    mixed widths (1, 81, 82 columns, as CPU ranks and GPU streams) get
    back what each gets alone, whatever shared its launch."""
    import threading
    import time
    n, n_threads = 64, 6
    end = _chain_end(n)
    dev = kb.DeviceAggregator(end)
    dev.inclusive(np.zeros((n, 1), np.float32))  # warm the jit cache
    if kind == "dense":
        reqs = [np.full((n, k + 1), float(k + 1), dtype=np.float32)
                for k in range(n_threads)]
        submit = dev.inclusive
        # chain tree: inclusive[i] = sum over [i, n) = (n - i) * v
        wants = [np.outer(n - np.arange(n), np.ones(k + 1)) * (k + 1)
                 for k in range(n_threads)]
    else:
        reqs = [_sparse_request(rng, n, (1, 81, 82)[k % 3], 40 + k)
                for k in range(n_threads)]
        submit = dev.inclusive_at
        wants = [submit(*r) for r in reqs]   # each alone in its launch
        for (rows, cols, vals, width, ir, ic), want in zip(reqs, wants):
            dense = np.zeros((n + 1, width))
            dense[rows, cols] = vals
            ps = np.cumsum(dense[::-1], axis=0)[::-1]   # sum over [i, n)
            np.testing.assert_array_equal(want, ps[ir, ic])
    barrier = threading.Barrier(n_threads)
    outs, errs = [None] * n_threads, [None] * n_threads

    def work(k):
        barrier.wait()
        try:
            outs[k] = submit(*reqs[k]) if kind == "sparse" else \
                submit(reqs[k])
        except BaseException as e:  # pragma: no cover - surfaced below
            errs[k] = e

    fn = dev._incl_fn
    held = []

    def hold_first_launch(*a, **kw):
        # the first launch waits until every other request is pending, so
        # that those share the next one
        if not held:
            held.append(True)
            while len(dev._pending) < n_threads - 1:
                time.sleep(0.001)
        return fn(*a, **kw)

    dev._incl_fn = hold_first_launch
    requests, launches = dev.requests, dev.launches
    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errs == [None] * n_threads
    for k in range(n_threads):
        assert outs[k].shape == wants[k].shape
        if kind == "dense":
            assert_allclose(outs[k], wants[k], rtol=1e-6)
        else:
            np.testing.assert_array_equal(outs[k], wants[k])
    assert dev.requests == requests + n_threads
    assert dev.launches == launches + 2


def test_device_aggregator_combine_sums_matches_bincount(rng):
    end = _chain_end(8)
    dev = kb.DeviceAggregator(end, offload_combine=True, combine_min=1)
    seg = np.sort(rng.integers(0, 50, 400)).astype(np.int32)
    vals = rng.integers(1, 5, 400).astype(np.float32)  # exact class
    got = dev.combine_sums(seg, vals)
    want = np.bincount(seg, weights=vals.astype(np.float64),
                       minlength=int(seg[-1]) + 1)
    np.testing.assert_array_equal(got, want)


def test_device_aggregator_error_wakes_all_waiters():
    """A launch failure must set the error on every batched request instead
    of leaving waiters parked forever."""
    end = _chain_end(16)
    dev = kb.DeviceAggregator(end)
    with pytest.raises(Exception):
        dev.inclusive(np.zeros((8, 2), np.float32))  # wrong leading dim


def test_device_offsets_matches_cumsum(rng):
    sizes = rng.integers(0, 1000, 333).astype(np.int64)
    got = kb.device_offsets(sizes)
    want = np.concatenate([[0], np.cumsum(sizes)])
    np.testing.assert_array_equal(got, want)
    assert kb.device_offsets(np.empty(0, np.int64)) is None
    big = np.array([np.iinfo(np.int32).max], np.int64)
    assert kb.device_offsets(big) is None  # int32 overflow guard
