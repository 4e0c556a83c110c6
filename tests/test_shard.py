"""Sharded query service: consistent-hash routing, byte-parity across
shard counts, scatter-gather merges, shm payload hygiene, and the
fault-injection suite — SIGKILL a worker mid-batch and prove the
supervisor's respawn + replay turns it into latency, not wrong answers."""
import os
import signal
import threading
import time

import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, st

from repro.core.aggregate import AggregationConfig, StreamingAggregator
from repro.query import Database, threshold_contexts, topk_hot_paths
from repro.serve.engine import QueryError, QueryRequest, QueryServer
from repro.serve.scheduler import BatchScheduler, Overloaded
from repro.serve.shard import (ConsistentHashRing, ShardedQueryServer,
                               _merge_scatter)
from repro.serve.warm import plan_warm
from tests.conftest import make_profile, shm_alive

N_PROFILES = 6


@pytest.fixture(scope="module")
def db_dir(tmp_path_factory):
    td = tmp_path_factory.mktemp("sharddb")
    rng = np.random.default_rng(23)
    paths = []
    for i in range(N_PROFILES):
        prof = make_profile(rng, n_nodes=90, n_metrics=6, density=0.3,
                            n_trace=24, identity={"rank": i})
        p = td / f"prof{i:03d}.rprf"
        prof.save(p)
        paths.append(str(p))
    StreamingAggregator(
        td / "db", AggregationConfig(executor="threads", n_workers=3)
    ).run(paths)
    return str(td / "db")


def _mixed_requests(db, n, seed=0):
    rng = np.random.default_rng(seed)
    ctxs, mids = db.stats["ctx"], db.stats["mid"]
    reqs = []
    for _ in range(n):
        i = int(rng.integers(len(ctxs)))
        pick = rng.random()
        if pick < 0.30:
            reqs.append(QueryRequest(op="stripe", ctx=int(ctxs[i]),
                                     metric=int(mids[i])))
        elif pick < 0.50:
            reqs.append(QueryRequest(
                op="profile", pid=int(rng.integers(db.n_profiles))))
        elif pick < 0.70:
            reqs.append(QueryRequest(op="value",
                                     pid=int(rng.integers(db.n_profiles)),
                                     ctx=int(ctxs[i]), metric=int(mids[i])))
        elif pick < 0.80:
            reqs.append(QueryRequest(op="topk", metric=0, inclusive=True,
                                     k=int(rng.integers(3, 12))))
        elif pick < 0.90:
            reqs.append(QueryRequest(op="threshold", metric=0,
                                     inclusive=True,
                                     params={"min_value":
                                             float(rng.uniform(0, 5))}))
        else:
            reqs.append(QueryRequest(
                op="window", pid=int(rng.integers(db.n_profiles)),
                t0=0.0, t1=0.7))
    return reqs


def _assert_bytes_equal(got, ref, where=""):
    """Byte-level equality across every result shape the ops produce."""
    if isinstance(ref, QueryError):
        assert got == ref, where
    elif hasattr(ref, "val"):                       # SparseMetrics plane
        assert got.encode() == ref.encode(), where
    elif hasattr(ref, "time"):                      # Trace window
        assert got.time.tobytes() == ref.time.tobytes(), where
        assert got.ctx.tobytes() == ref.ctx.tobytes(), where
    elif isinstance(ref, tuple):                    # stripe / threshold
        assert got[0].dtype == ref[0].dtype, where
        assert got[1].dtype == ref[1].dtype, where
        assert got[0].tobytes() == ref[0].tobytes(), where
        assert got[1].tobytes() == ref[1].tobytes(), where
    else:                                           # float / topk rows
        assert got == ref, where


# ---------------------------------------------------------------------------
# consistent-hash ring
# ---------------------------------------------------------------------------

def test_ring_routing_is_deterministic_and_balanced():
    ring = ConsistentHashRing(4)
    keys = [(g, i) for g in (0, 1) for i in range(2000)]
    owners = np.array([ring.route_key(k) for k in keys])
    again = ConsistentHashRing(4)
    assert [again.route_key(k) for k in keys] == owners.tolist()
    shares = np.bincount(owners, minlength=4) / owners.size
    assert shares.min() > 0.10 and shares.max() < 0.45, shares


def test_ring_growth_moves_only_keys_to_the_new_shard():
    """The consistent-hashing contract, exactly: growing N -> N+1 only
    adds ring points, so every key that changes owner moves TO the new
    shard, and the moved fraction is ~1/(N+1)."""
    keys = [(g, i) for g in (0, 1) for i in range(3000)]
    for n in (2, 3, 4, 7):
        old = ConsistentHashRing(n)
        new = ConsistentHashRing(n + 1)
        moved = 0
        for k in keys:
            a, b = old.route_key(k), new.route_key(k)
            if a != b:
                assert b == n, f"key {k} moved {a}->{b}, not to new shard {n}"
                moved += 1
        frac = moved / len(keys)
        expect = 1.0 / (n + 1)
        assert frac < 2.0 * expect + 0.02, (n, frac, expect)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 12), st.lists(st.tuples(st.integers(0, 2),
                                              st.integers(0, 10**6)),
                                    min_size=1, max_size=200))
def test_ring_stability_property(n_shards, keys):
    """Property form: any key population, any shard count — every route
    is in range, stable across instances, and growth only moves keys to
    the newcomer."""
    ring = ConsistentHashRing(n_shards)
    grown = ConsistentHashRing(n_shards + 1)
    for k in keys:
        a = ring.route_key(k)
        assert 0 <= a < n_shards
        b = grown.route_key(k)
        assert a == b or b == n_shards


def test_ring_ownership_partitions_contexts():
    ring = ConsistentHashRing(3)
    owned = [set(ring.owned_contexts(500, s).tolist()) for s in range(3)]
    assert not (owned[0] & owned[1] or owned[0] & owned[2]
                or owned[1] & owned[2])
    assert owned[0] | owned[1] | owned[2] == set(range(500))
    mask = ring.owned_context_mask(500, 1)
    assert set(np.flatnonzero(mask).tolist()) == owned[1]


# ---------------------------------------------------------------------------
# byte-parity: sharded vs in-process, every op, shards = 1 | 2 | 4
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_sharded_parity_every_op(db_dir, n_shards):
    with Database(db_dir) as db:
        reqs = _mixed_requests(db, 80, seed=n_shards)
        reqs += [QueryRequest(op="nope"),                 # unknown op
                 QueryRequest(op="profile", pid=10**6),   # bad id
                 QueryRequest(op="stripe", ctx=0, metric="no_such_name"),
                 QueryRequest(op="topk", metric="no_such_name"),
                 QueryRequest(op="threshold", metric="no_such_name")]
        ref = [QueryServer(db).serve_one(r) for r in reqs]
    with ShardedQueryServer(db_dir, n_shards, slab_bytes=1 << 20,
                            n_slabs=4) as srv:
        got = srv.serve(reqs)
        for i, (g, r) in enumerate(zip(got, ref)):
            _assert_bytes_equal(g, r, f"shards={n_shards} slot={i} "
                                      f"op={reqs[i].op}")
        m = srv.metrics()
        assert m["completed"] == m["dispatched"]
        assert m["respawns"] == 0


def test_scatter_merge_matches_single_space_order(db_dir):
    """Partial top-k/threshold merges reproduce the exact deterministic
    (-value, ctx) order of the single-space select functions."""
    with Database(db_dir) as db:
        ring = ConsistentHashRing(3)
        masks = [ring.owned_context_mask(db.n_contexts, s) for s in range(3)]
        req = QueryRequest(op="topk", metric=0, inclusive=True, k=8)
        parts = [topk_hot_paths(db, 0, k=8, inclusive=True, within=m)
                 for m in masks]
        assert _merge_scatter(req, parts) == topk_hot_paths(
            db, 0, k=8, inclusive=True)
        treq = QueryRequest(op="threshold", metric=0, inclusive=True,
                            params={"min_value": 0.5})
        tparts = [threshold_contexts(db, 0, min_value=0.5, inclusive=True,
                                     within=m) for m in masks]
        got = _merge_scatter(treq, tparts)
        ref = threshold_contexts(db, 0, min_value=0.5, inclusive=True)
        _assert_bytes_equal(got, ref)


def test_window_dedupe_coalesces_identical_requests(db_dir):
    with ShardedQueryServer(db_dir, 2, slab_bytes=1 << 20) as srv:
        req = QueryRequest(op="profile", pid=1)
        out = srv.serve([req] * 12 + [QueryRequest(op="profile", pid=2)])
        assert all(o.encode() == out[0].encode() for o in out[:12])
        m = srv.metrics()
        assert m["deduped"] == 11
        # 12 identical fetches cost ONE dispatch (plus the odd one out)
        assert m["dispatched"] == 2


# ---------------------------------------------------------------------------
# fault injection: SIGKILL, replay, poison, shm hygiene
# ---------------------------------------------------------------------------

class _SleepKillServer(QueryServer):
    """Worker-side test double: ``sleep`` stalls, ``die`` SIGKILLs the
    worker process mid-batch (module-level so any mp start method can
    ship it to workers)."""

    def submit(self, req):
        if req.op == "sleep":
            time.sleep(req.t0)
            return 0.0
        if req.op == "die":
            os.kill(os.getpid(), signal.SIGKILL)
        return super().submit(req)


@pytest.mark.skipif(not hasattr(signal, "SIGKILL"), reason="POSIX only")
def test_sigkill_mid_batch_replays_to_respawned_worker(db_dir, own_shm):
    """Kill the worker serving a batch: the supervisor respawns it,
    replays the unanswered requests, and every client future resolves
    with byte-correct results — a crash costs latency, never answers."""
    with Database(db_dir) as db:
        ref = [QueryServer(db).serve_one(QueryRequest(op="profile", pid=p))
               for p in range(N_PROFILES)]
    with ShardedQueryServer(db_dir, 2, slab_bytes=1 << 20,
                            server_factory=_SleepKillServer) as srv:
        sleep_req = QueryRequest(op="sleep", t0=0.6)
        victim = srv.shard_of(sleep_req)
        reqs = [sleep_req] + [QueryRequest(op="profile", pid=p)
                              for p in range(N_PROFILES)]
        out: list = [None]
        t = threading.Thread(
            target=lambda: out.__setitem__(0, srv.serve(reqs)))
        t.start()
        time.sleep(0.2)               # victim worker is inside the sleep
        os.kill(srv.worker_pids()[victim], signal.SIGKILL)
        t.join(30)
        assert not t.is_alive(), "serve() wedged after worker death"
        got = out[0]
        assert got[0] == 0.0, f"replayed sleep answered {got[0]!r}"
        for g, r in zip(got[1:], ref):
            _assert_bytes_equal(g, r)
        m = srv.metrics()
        assert m["respawns"] >= 1 and m["replayed"] >= 1
        assert m["shards"][victim]["deaths"] >= 1
        # the respawned worker keeps serving this shard correctly
        again = srv.serve_one(QueryRequest(op="profile", pid=2))
        _assert_bytes_equal(again, ref[2])
    time.sleep(0.2)
    assert own_shm(), "the server made no slabs"
    assert not shm_alive(own_shm()), "worker death leaked /dev/shm"


@pytest.mark.skipif(not hasattr(signal, "SIGKILL"), reason="POSIX only")
def test_poison_request_resolves_worker_lost_not_forever(db_dir):
    """A request that deterministically kills its worker must not replay
    forever: after replay_limit respawns it resolves to a structured
    WorkerLost error, and the shard keeps serving everyone else."""
    with Database(db_dir) as db:
        ref = QueryServer(db).serve_one(QueryRequest(op="profile", pid=1))
    with ShardedQueryServer(db_dir, 2, slab_bytes=1 << 20, replay_limit=2,
                            server_factory=_SleepKillServer) as srv:
        t0 = time.monotonic()
        res = srv.serve_one(QueryRequest(op="die"))
        assert time.monotonic() - t0 < 60
        assert isinstance(res, QueryError) and res.error == "WorkerLost"
        m = srv.metrics()
        assert m["worker_lost"] == 1
        assert m["respawns"] >= srv.replay_limit
        _assert_bytes_equal(
            srv.serve_one(QueryRequest(op="profile", pid=1)), ref)


def test_close_unlinks_all_slabs(db_dir, own_shm):
    srv = ShardedQueryServer(db_dir, 3, n_slabs=4, slab_bytes=1 << 16)
    srv.start()
    created = own_shm()
    assert len(created) == 12                   # 3 shards x 4 slabs
    assert shm_alive(created) == set(created)
    srv.serve([QueryRequest(op="profile", pid=0)])
    srv.close()
    time.sleep(0.2)
    assert own_shm() == created
    assert not shm_alive(created), "close() left shm segments"
    srv.close()  # idempotent


# ---------------------------------------------------------------------------
# scheduler integration: per-shard admission, parity under concurrency
# ---------------------------------------------------------------------------

def test_scheduler_parity_with_concurrent_clients(db_dir):
    n_clients, per_client = 8, 20
    with Database(db_dir) as db:
        reqs = _mixed_requests(db, n_clients * per_client, seed=9)
        ref = [QueryServer(db).serve_one(r) for r in reqs]
    with ShardedQueryServer(db_dir, 2, slab_bytes=1 << 20) as srv:
        with BatchScheduler(srv, max_queue=1024) as sched:
            assert sched.metrics()["direct_dispatch"] is True
            results: list = [None] * len(reqs)

            def client(k):
                for j in range(per_client):
                    i = k * per_client + j
                    results[i] = sched.submit(reqs[i]).result(30)

            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stats = sched.metrics()
    for i, (got, r) in enumerate(zip(results, ref)):
        _assert_bytes_equal(got, r, f"slot={i} op={reqs[i].op}")
    assert stats["completed"] == len(reqs)
    assert stats["errors"] == 0


def test_scheduler_per_shard_admission_bounds(db_dir):
    """Admission is per shard: saturating one shard 429s traffic bound
    for it while the other shard keeps admitting and serving."""
    # replicas=1 pins each key to exactly one shard; with R>1 the router
    # would spill the backlog onto the replica instead of 429ing
    with ShardedQueryServer(db_dir, 2, slab_bytes=1 << 20, replicas=1,
                            server_factory=_SleepKillServer) as srv:
        sleeper = QueryRequest(op="sleep", t0=0.8)
        hot = srv.shard_of(sleeper)
        # a profile request routed to the OTHER shard
        other_pid = next(p for p in range(N_PROFILES)
                         if srv.shard_of(QueryRequest(op="profile", pid=p))
                         != hot)
        with BatchScheduler(srv, max_queue=4) as sched:
            stalled = [sched.submit(sleeper) for _ in range(4)]
            with pytest.raises(Overloaded) as exc:
                for _ in range(8):
                    sched.submit(sleeper)
            assert exc.value.retry_after_s > 0
            # the cold shard still admits and serves immediately
            res = sched.submit(QueryRequest(op="profile", pid=other_pid)
                               ).result(10)
            assert not isinstance(res, QueryError)
            for f in stalled:
                assert f.result(30) == 0.0
            assert sched.metrics()["rejected"] > 0


# ---------------------------------------------------------------------------
# shard-aware warming
# ---------------------------------------------------------------------------

def test_warm_plans_partition_across_shards(db_dir):
    """Each shard's warm plan covers exactly the planes it owns: plans
    are disjoint across shards and union to the unsharded plan."""
    ring = ConsistentHashRing(3)
    with Database(db_dir) as db:
        full = set((s, o) for s, o, _ in plan_warm(db, 1 << 30))
        per_shard = []
        for s in range(3):
            plan = plan_warm(db, 1 << 30,
                             owned=lambda st, oid, s=s:
                             ring.owns_plane(st, oid, s))
            for store, oid, _ in plan:
                assert ring.owns_plane(store, oid, s)
            per_shard.append(set((st, o) for st, o, _ in plan))
    assert per_shard[0] | per_shard[1] | per_shard[2] == full
    assert not (per_shard[0] & per_shard[1])
    assert not (per_shard[0] & per_shard[2])
    assert not (per_shard[1] & per_shard[2])


def test_workers_warm_only_owned_planes(db_dir):
    # replicas=1: plans partition exactly (with R>1 replica-owned planes
    # are deliberately planned by several workers — see test_replication)
    with ShardedQueryServer(db_dir, 2, warm_bytes=None, replicas=1,
                            slab_bytes=1 << 20) as srv:
        reports = srv.warm_reports()
        assert len(reports) == 2
        assert all(r["warm"]["loaded"] > 0 for r in reports)
        with Database(db_dir) as db:
            full = len(plan_warm(db, int((64 << 20) * 0.9)))
        total = sum(r["warm"]["planned"] for r in reports)
        assert total <= full  # each plane planned by at most one worker


# ---------------------------------------------------------------------------
# HTTP transport end to end with shards
# ---------------------------------------------------------------------------

def test_http_sharded_roundtrip(db_dir):
    from repro.serve.client import QueryClient
    from repro.serve.http import QueryHTTPServer
    with Database(db_dir) as db:
        ctx = int(db.stats["ctx"][0])
        mid = int(db.stats["mid"][0])
        with QueryHTTPServer(db, port=0, shards=2,
                             shard_slab_bytes=1 << 20) as srv:
            host, port = srv.address
            with QueryClient(host, port) as cl:
                health = cl.health()
                assert health["status"] == "ok" and health["shards"] == 2
                sm = cl.profile(1)
                ref = db.profile_metrics(1)
                assert sm.encode() == ref.encode()
                prof, vals = cl.stripe(ctx, mid)
                rprof, rvals = db.stripe(ctx, mid)
                np.testing.assert_array_equal(prof, rprof)
                np.testing.assert_allclose(vals, rvals)
                assert cl.topk(0, k=4) == topk_hot_paths(db, 0, k=4)
                m = cl.metrics()
                assert m["shards"]["n_shards"] == 2
                assert m["shards"]["completed"] >= 2
                assert m["warm"]["sharded"][0]["warm"] is None \
                    or m["warm"]["sharded"][0]["warm"]["loaded"] >= 0
