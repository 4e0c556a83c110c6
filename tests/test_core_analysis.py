"""Propagation, statistics, PMS/CMS, dense-baseline correctness."""
import numpy as np
import pytest

# optional dep: property tests skip without hypothesis, the rest run
from tests._hypothesis_compat import given, settings, st

from repro.core.cct import ContextTree
from repro.core import cms as cms_mod
from repro.core.cms import (CMSReader, _encode_group, _gather_group_heap,
                            _gather_group_vectorized, build_cms, census)
from repro.core.dense_baseline import DenseAnalysis
from repro.core.metrics import INCLUSIVE_BIT
from repro.core.pms import PMSReader, PMSWriter
from repro.core.propagate import (propagate_inclusive,
                                  propagate_inclusive_reference,
                                  redistribute_placeholders)
from repro.core.sparse import SparseMetrics
from repro.core.stats import StatsAccumulator
from repro.core.traces import TraceDBReader, TraceDBWriter
from repro.core.sparse import Trace
from repro.utils import binio
from tests.conftest import make_profile, random_sparse, random_tree


# ---------------------------------------------------------------------------
# propagation (paper §4.1.2)
# ---------------------------------------------------------------------------

def test_propagate_matches_recursive_walk(rng):
    t = random_tree(rng, 80)
    sm = random_sparse(rng, len(t), 6, 0.15)
    pos, order, end = t.preorder()
    fast = propagate_inclusive(sm, pos, end)
    slow = propagate_inclusive_reference(sm, t.parent_array())
    np.testing.assert_array_equal(fast.ctx, slow.ctx)
    np.testing.assert_array_equal(fast.mid, slow.mid)
    np.testing.assert_allclose(fast.val, slow.val, rtol=1e-12)


def test_propagate_root_inclusive_is_total(rng):
    t = random_tree(rng, 50)
    sm = random_sparse(rng, len(t), 3, 0.2)
    pos, order, end = t.preorder()
    out = propagate_inclusive(sm, pos, end)
    rows, mids, vals = sm.triplets()
    for m in np.unique(mids):
        assert out.lookup(0, int(m) | INCLUSIVE_BIT) == pytest.approx(
            vals[mids == m].sum()
        )


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 60), st.integers(0, 2**31 - 1))
def test_property_propagation_conservation(n_nodes, seed):
    """Inclusive at any node == sum of exclusives in its subtree."""
    rng = np.random.default_rng(seed)
    t = random_tree(rng, n_nodes)
    sm = random_sparse(rng, len(t), 4, 0.3)
    pos, order, end = t.preorder()
    out = propagate_inclusive(sm, pos, end)
    dense_ex = sm.to_dense(len(t), 4)
    parent = t.parent_array()
    # check a handful of nodes against brute-force subtree sums
    for node in rng.choice(len(t), size=min(8, len(t)), replace=False):
        subtree = [int(node)]
        members = set(subtree)
        changed = True
        while changed:
            changed = False
            for c in range(len(t)):
                if c not in members and int(parent[c]) in members:
                    members.add(c)
                    changed = True
        for m in range(4):
            expect = sum(dense_ex[c, m] for c in members)
            got = out.lookup(int(node), m | INCLUSIVE_BIT)
            assert np.isclose(got, expect, rtol=1e-9, atol=1e-12)


def test_redistribute_placeholders():
    # placeholder ctx 5 splits 60/40 across leaves 7, 9 (paper §4.1.3)
    sm = SparseMetrics.from_triplets([5, 2], [1, 1], [10.0, 3.0])
    routes = {5: (np.array([7, 9]), np.array([6.0, 4.0]))}
    out = redistribute_placeholders(sm, routes)
    assert out.lookup(7, 1) == pytest.approx(6.0)
    assert out.lookup(9, 1) == pytest.approx(4.0)
    assert out.lookup(5, 1) == 0.0
    assert out.lookup(2, 1) == 3.0


# ---------------------------------------------------------------------------
# statistics (paper §4.1.2 / §4.2.2)
# ---------------------------------------------------------------------------

def test_stats_match_dense(rng):
    n_ctx, n_met, P = 40, 6, 16
    mats = [rng.uniform(0, 1, (n_ctx, n_met)) for _ in range(P)]
    for m in mats:
        m[m < 0.5] = 0.0
    acc = StatsAccumulator()
    for m in mats:
        acc.update(SparseMetrics.from_dense(m))
    out = acc.finalize()
    stack = np.stack(mats)  # (P, C, M)
    for i in range(len(out["ctx"])):
        c, m = int(out["ctx"][i]), int(out["mid"][i])
        col = stack[:, c, m]
        nz = col[col != 0]
        assert out["count"][i] == nz.size
        assert out["sum"][i] == pytest.approx(nz.sum())
        assert out["mean"][i] == pytest.approx(nz.mean())
        assert out["min"][i] == pytest.approx(nz.min())
        assert out["max"][i] == pytest.approx(nz.max())
        assert out["std"][i] == pytest.approx(nz.std(), abs=1e-9)


def test_stats_merge_equals_single(rng):
    sms = [random_sparse(rng, 30, 5, 0.2) for _ in range(8)]
    one = StatsAccumulator()
    for s in sms:
        one.update(s)
    left, right = StatsAccumulator(), StatsAccumulator()
    for s in sms[:3]:
        left.update(s)
    for s in sms[3:]:
        right.update(s)
    left.merge(right)
    a, b = one.finalize(), left.finalize()
    np.testing.assert_array_equal(a["ctx"], b["ctx"])
    for k in ("sum", "count", "mean", "min", "max", "std"):
        np.testing.assert_allclose(a[k], b[k], rtol=1e-12)


def test_stats_serialization_roundtrip(rng):
    acc = StatsAccumulator()
    acc.update(random_sparse(rng, 20, 4, 0.3))
    acc2 = StatsAccumulator.from_arrays(acc.to_arrays())
    a, b = acc.finalize(), acc2.finalize()
    np.testing.assert_allclose(a["sum"], b["sum"])


# ---------------------------------------------------------------------------
# PMS (paper §3.2 profile-major)
# ---------------------------------------------------------------------------

def test_pms_write_read_out_of_order(tmp_path, rng):
    P = 6
    planes = [random_sparse(rng, 50, 8, 0.2) for _ in range(P)]
    tree = random_tree(rng, 50)
    w = PMSWriter(tmp_path / "db.pms", P)
    for pid in reversed(range(P)):  # out-of-order writes are legal
        w.add_plane(pid, planes[pid], identity={"rank": pid})
    w.finalize(tree=tree, registry_json=[], stats=None)
    r = PMSReader(tmp_path / "db.pms")
    assert r.n_profiles == P
    for pid in range(P):
        got = r.plane(pid)
        np.testing.assert_allclose(got.val, planes[pid].val)
        np.testing.assert_array_equal(got.ctx, planes[pid].ctx)
        assert r.identity(pid) == {"rank": pid}
    assert len(r.tree) == len(tree)
    r.close()


def test_pms_query(tmp_path, rng):
    sm = SparseMetrics.from_triplets([2, 4], [1, 3], [7.5, 2.5])
    w = PMSWriter(tmp_path / "db.pms", 1)
    w.add_plane(0, sm)
    w.finalize()
    with PMSReader(tmp_path / "db.pms") as r:
        assert r.query(0, 2, 1) == 7.5
        assert r.query(0, 4, 3) == 2.5
        assert r.query(0, 2, 3) == 0.0


def test_pms_stats_persist(tmp_path, rng):
    acc = StatsAccumulator()
    acc.update(random_sparse(rng, 20, 4, 0.5))
    stats = acc.finalize()
    w = PMSWriter(tmp_path / "db.pms", 1)
    w.add_plane(0, random_sparse(rng, 20, 4, 0.5))
    w.finalize(stats={k: np.asarray(v, np.float64) for k, v in stats.items()})
    with PMSReader(tmp_path / "db.pms") as r:
        np.testing.assert_allclose(r.stats["sum"], stats["sum"])


# ---------------------------------------------------------------------------
# CMS (paper §3.2 context-major, §4.3.2 builder)
# ---------------------------------------------------------------------------

def _build_pms(tmp_path, rng, P=8, n_ctx=60, n_met=8, density=0.15):
    planes = [random_sparse(rng, n_ctx, n_met, density) for _ in range(P)]
    tree = ContextTree()
    for i in range(n_ctx - 1):
        tree.child(int(rng.integers(0, len(tree))), 2, f"n{i}")
    w = PMSWriter(tmp_path / "db.pms", P)
    for pid, sm in enumerate(planes):
        w.add_plane(pid, sm)
    w.finalize(tree=tree)
    return planes, tmp_path / "db.pms"


@pytest.mark.parametrize("strategy", ["vectorized", "heap"])
@pytest.mark.parametrize("balance", ["dynamic", "static"])
def test_cms_matches_pms(tmp_path, rng, strategy, balance):
    planes, pms_path = _build_pms(tmp_path, rng)
    cms_path = tmp_path / f"db.{strategy}.{balance}.cms"
    build_cms(pms_path, cms_path, n_workers=3, strategy=strategy,
              balance=balance, group_target_bytes=512)
    with CMSReader(cms_path) as r:
        for pid, sm in enumerate(planes):
            rows, mids, vals = sm.triplets()
            for c, m, v in zip(rows, mids, vals):
                assert r.query(int(c), int(m), pid) == pytest.approx(v)


def test_cms_strategies_byte_identical(tmp_path, rng):
    _, pms_path = _build_pms(tmp_path, rng)
    build_cms(pms_path, tmp_path / "a.cms", strategy="vectorized", n_workers=2)
    build_cms(pms_path, tmp_path / "b.cms", strategy="heap", n_workers=2)
    assert (tmp_path / "a.cms").read_bytes() == (tmp_path / "b.cms").read_bytes()


def test_cms_stripe_contiguous(tmp_path, rng):
    planes, pms_path = _build_pms(tmp_path, rng, P=10)
    build_cms(pms_path, tmp_path / "db.cms", n_workers=2)
    with CMSReader(tmp_path / "db.cms") as r:
        # stripe = all profiles' values for (ctx, metric); compare vs planes
        for ctx in range(0, 60, 7):
            for mid in range(8):
                prof, vals = r.stripe(ctx, mid)
                expect = {p: planes[p].lookup(ctx, mid) for p in range(10)
                          if planes[p].lookup(ctx, mid) != 0.0}
                assert {int(p): v for p, v in zip(prof, vals)} == pytest.approx(expect)
                assert np.all(np.diff(prof.astype(np.int64)) > 0)  # sorted profiles


def test_census_sizes_exact(tmp_path, rng):
    planes, pms_path = _build_pms(tmp_path, rng)
    pms = PMSReader(pms_path)
    x_c, m_c = census(pms, 60)
    # census matches brute force
    for c in range(60):
        pairs = [(p, int(m)) for p, sm in enumerate(planes)
                 for m in sm.context_slice(c)[0]]
        assert x_c[c] == len(pairs)
        assert m_c[c] == len({m for _, m in pairs})
    pms.close()


def _reference_planes(planes, lo, hi) -> bytes:
    """Each context of [lo, hi) with data, one ``binio.pack_array`` per
    block: mids u16[m], mstart u64[m+1], prof u32[x], vals f64[x]."""
    out = b""
    for c in range(lo, hi):
        cells = sorted((m, p, v) for p, sm in enumerate(planes)
                       for m, v in zip(*sm.context_slice(c)))
        if not cells:
            continue
        starts = {}
        for i, (m, _, _) in enumerate(cells):
            starts.setdefault(m, i)
        umids = list(starts)
        mstart = list(starts.values()) + [len(cells)]
        out += (binio.pack_array(np.array(umids, np.uint16))
                + binio.pack_array(np.array(mstart, np.uint64))
                + binio.pack_array(np.array([p for _, p, _ in cells], np.uint32))
                + binio.pack_array(np.array([v for _, _, v in cells], np.float64)))
    return out


def _planes(*triplets):
    return [SparseMetrics.from_triplets(*(np.asarray(a) for a in t))
            for t in triplets]


_NO_ENTRIES = ([], [], [])

# Each case: its profiles' planes (built from rng) and the group [lo, hi).
_GROUP_CASES = {
    "empty": lambda rng: (_planes(([5], [1], [1.0]), _NO_ENTRIES), 0, 5),
    "one_value": lambda rng: (_planes(([3], [7], [2.5])), 0, 10),
    "all_83_metrics": lambda rng: (_planes(
        *(([4] * 83, np.arange(83), rng.uniform(1, 2, 83)) for _ in range(3))),
        0, 8),
    "mids_near_65535": lambda rng: (_planes(
        ([1, 1, 2], [65533, 65535, 65534], [1.0, 2.0, 3.0]),
        ([1, 2, 2], [65535, 0, 65535], [4.0, 5.0, 6.0])), 0, 3),
    "profile_without_entries": lambda rng: (_planes(
        ([0, 2], [1, 1], [1.0, 2.0]), ([40], [3], [9.0]),
        ([2, 3], [0, 1], [3.0, 4.0])), 0, 10),
    # one context whose plane is larger than the 1 MiB group target
    "context_above_group_target": lambda rng: (_planes(
        *(([6] * 2200, np.arange(2200), rng.exponential(1.0, 2200))
          for _ in range(40))), 0, 10),
    "first_context_after_lo": lambda rng: (_planes(
        *((rng.integers(23, 60, 40), rng.integers(0, 8, 40),
           rng.uniform(1, 2, 40)) for _ in range(6)),
        ([5, 57], [1, 2], [1.0, 1.0])), 20, 50),
}


@pytest.mark.parametrize("case", list(_GROUP_CASES))
def test_encode_group_matches_per_context_pack_array(tmp_path, rng, case):
    """The group encoder writes, byte for byte, what one ``pack_array`` per
    block of each context writes; so does the gather that feeds it."""
    planes, lo, hi = _GROUP_CASES[case](rng)
    w = PMSWriter(tmp_path / "db.pms", len(planes))
    for pid, sm in enumerate(planes):
        w.add_plane(pid, sm)
    w.finalize()
    want = _reference_planes(planes, lo, hi)
    if case == "context_above_group_target":
        assert len(want) > 1 << 20
    rows, mids, pids, vals = (np.concatenate(a) for a in zip(*(
        (*sm.triplets()[:2], np.full(sm.val.size, p), sm.val)
        for p, sm in enumerate(planes))))
    keep = (rows >= lo) & (rows < hi)
    order = np.lexsort((pids[keep], mids[keep], rows[keep]))
    got = _encode_group(*(a[keep][order] for a in (rows, mids, pids, vals)))
    assert got == want
    with PMSReader(tmp_path / "db.pms") as pms:
        group = _gather_group_vectorized(pms, lo, hi)
        heap = _gather_group_heap(pms, lo, hi)
    if not want:
        assert group is None and heap is None
        return
    first = min(c for sm in planes for c in sm.ctx.tolist() if lo <= c < hi)
    n_planes = len({c for sm in planes for c in sm.ctx.tolist() if lo <= c < hi})
    assert group == heap == (first, n_planes, want)
    if case == "first_context_after_lo":
        assert first > lo


def test_vectorized_cms_encodes_no_plane_block_by_block(tmp_path, rng,
                                                        monkeypatch):
    """The vectorised build never calls ``binio.pack_array``: no context
    is encoded on its own (the heap strategy, the control, does)."""
    _, pms_path = _build_pms(tmp_path, rng)
    calls = []
    pack_array = binio.pack_array

    def counted(arr):
        calls.append(arr.size)
        return pack_array(arr)

    monkeypatch.setattr(cms_mod.binio, "pack_array", counted)
    build_cms(pms_path, tmp_path / "v.cms", strategy="vectorized",
              n_workers=2, group_target_bytes=512)
    assert calls == []
    build_cms(pms_path, tmp_path / "h.cms", strategy="heap", n_workers=2,
              group_target_bytes=512)
    assert calls
    assert (tmp_path / "v.cms").read_bytes() == (tmp_path / "h.cms").read_bytes()


# ---------------------------------------------------------------------------
# dense baseline (HPCToolkit analog)
# ---------------------------------------------------------------------------

def test_dense_analysis_matches_sparse_propagation(tmp_path, rng):
    profs = [make_profile(rng, n_nodes=25, n_metrics=5) for _ in range(4)]
    paths = []
    for i, p in enumerate(profs):
        path = tmp_path / f"p{i}.rprf"
        p.save(path)
        paths.append(str(path))
    da = DenseAnalysis(tmp_path / "dense.npy")
    res = da.run(paths)
    # cross-check a profile's inclusive values against the sparse path
    unified = ContextTree()
    remaps = [unified.merge(p.tree) for p in profs]
    pos, order, end = unified.preorder()
    for i, (p, remap) in enumerate(zip(profs, remaps)):
        sm = p.metrics.remap_contexts(remap)
        out = propagate_inclusive(sm, pos, end)
        rows, mids, vals = out.triplets()
        for c, m, v in zip(rows[:50], mids[:50], vals[:50]):
            got = da.query(i, int(c), int(m))
            assert got == pytest.approx(v), (i, c, m)


# ---------------------------------------------------------------------------
# integrated trace DB (paper footnote 2)
# ---------------------------------------------------------------------------

def test_trace_db_roundtrip(tmp_path, rng):
    traces = [Trace(np.sort(rng.uniform(0, 1, n)),
                    rng.integers(0, 50, n).astype(np.uint32))
              for n in (5, 0, 17)]
    w = TraceDBWriter(tmp_path / "db.trc", [t.time.size for t in traces])
    for i in (2, 0, 1):  # parallel/out-of-order writes are legal
        w.write_trace(i, traces[i])
    w.close()
    r = TraceDBReader(tmp_path / "db.trc")
    assert r.n == 3
    for i, t in enumerate(traces):
        got = r.trace(i)
        np.testing.assert_allclose(got.time, t.time)
        np.testing.assert_array_equal(got.ctx, t.ctx)
    r.close()
