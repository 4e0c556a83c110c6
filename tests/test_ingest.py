"""Live ingest tier: incremental-append byte parity, versioned snapshots,
and epoch-following query service.

The load-bearing claims proved here:

* appending profiles in increments through :class:`IngestState` publishes
  databases **byte-identical** to one-shot ``StreamingAggregator.run``
  over the same profiles, on every executor;
* a publish that crashes mid-write leaves ``CURRENT`` valid and no
  staging litter; retention GC never deletes the current or a pinned
  epoch;
* a live query server (``--follow``) picks up new epochs without restart
  — sharded and single-process — and every batched reply is internally
  single-epoch even while epochs publish mid-stream.
"""
import filecmp
import os
import threading
import time

import numpy as np
import pytest

from repro.core.aggregate import AggregationConfig, StreamingAggregator
from repro.ingest import (IngestClient, IngestHTTPServer, IngestState,
                          SnapshotStore, epoch_dirname, read_current,
                          read_manifest)
from repro.query import Database, EpochSwitcher
from repro.serve.client import (QueryClient, ServerOverloaded,
                                TransportError)
from repro.serve.engine import QueryError, QueryRequest, QueryServer
from repro.serve.http import QueryHTTPServer
from repro.serve.wire import result_to_wire
from tests.conftest import make_profile

DB_FILES = ("db.pms", "db.cms", "db.trc")


def _write_profiles(dirpath, n, *, seed=7, start=0):
    rng = np.random.default_rng(seed)
    paths = []
    for i in range(n):
        prof = make_profile(rng, n_nodes=40, n_metrics=6, density=0.3,
                            n_trace=10,
                            identity={"rank": start + i,
                                      "host": f"h{(start + i) % 3}"})
        path = os.path.join(str(dirpath), f"p{start + i:03d}.rprf")
        prof.save(path)
        paths.append(path)
    return paths


def _serial_cfg(**kw):
    return AggregationConfig(executor="serial", **kw)


# ---------------------------------------------------------------------------
# incremental append == one-shot rebuild, to the byte
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("executor", ["serial", "threads", "processes"])
def test_incremental_append_matches_oneshot(tmp_path, executor):
    paths = _write_profiles(tmp_path, 12)
    state = IngestState(config=AggregationConfig(executor=executor,
                                                 n_workers=3))
    # uneven increments, including a single-profile append
    for lo, hi in ((0, 5), (5, 6), (6, 12)):
        state.append(paths[lo:hi])
    assert state.n_profiles == 12
    inc = tmp_path / "incremental"
    stats = state.write_database(inc)
    assert stats["n_profiles"] == 12

    one = tmp_path / "oneshot"
    StreamingAggregator(one, AggregationConfig(executor=executor,
                                               n_workers=3)).run(paths)
    for name in DB_FILES:
        assert filecmp.cmp(str(inc / name), str(one / name),
                           shallow=False), f"{name} diverged ({executor})"


def test_append_is_all_or_nothing(tmp_path):
    paths = _write_profiles(tmp_path, 4)
    bad = os.path.join(str(tmp_path), "bad.rprf")
    with open(bad, "wb") as f:
        f.write(b"RPRF but not really a profile")
    state = IngestState(config=_serial_cfg())
    state.append(paths[:2])
    with pytest.raises(Exception):
        state.append([paths[2], bad])  # fails mid-batch
    assert state.n_profiles == 2  # the poisoned batch left no residue
    state.append(paths[2:])  # and the state is still usable

    inc = tmp_path / "inc"
    state.write_database(inc)
    one = tmp_path / "one"
    StreamingAggregator(one, _serial_cfg()).run(paths)
    for name in DB_FILES:
        assert filecmp.cmp(str(inc / name), str(one / name), shallow=False)


# ---------------------------------------------------------------------------
# snapshot store: atomic publish, crash safety, retention + pins
# ---------------------------------------------------------------------------

def test_publish_crash_leaves_current_valid(tmp_path):
    root = str(tmp_path / "live")
    store = SnapshotStore(root)
    state = IngestState(config=_serial_cfg())
    state.append(_write_profiles(tmp_path, 3))

    epoch1, dir1 = store.publish(state.write_database)
    assert read_current(root) == (epoch1, dir1)
    manifest = read_manifest(dir1)
    assert manifest["epoch"] == epoch1
    for name, nbytes in manifest["files"].items():
        assert os.path.getsize(os.path.join(dir1, name)) == nbytes

    class Boom(RuntimeError):
        pass

    def bad_write(stage):
        state.write_database(stage)  # files partially/fully staged...
        raise Boom("crash between write and rename")

    with pytest.raises(Boom):
        store.publish(bad_write)
    # CURRENT still points at the good epoch; the staging dir is gone
    assert read_current(root) == (epoch1, dir1)
    assert not [n for n in os.listdir(root) if n.startswith(".tmp-")]
    with Database(dir1) as db:
        assert db.n_profiles == 3

    epoch2, dir2 = store.publish(state.write_database)
    assert epoch2 == epoch1 + 1
    assert read_current(root) == (epoch2, dir2)


def test_gc_keeps_current_and_pinned(tmp_path):
    root = str(tmp_path / "live")
    store = SnapshotStore(root)
    state = IngestState(config=_serial_cfg())
    state.append(_write_profiles(tmp_path, 2))

    e1, d1 = store.publish(state.write_database)
    e2, d2 = store.publish(state.write_database)
    pin = store.pin(e1)
    e3, d3 = store.publish(state.write_database)
    e4, d4 = store.publish(state.write_database)

    removed = store.gc(retain=1)
    # e1 is pinned and e4 is current: both survive; e2/e3 are fair game
    assert os.path.isdir(d1) and os.path.isdir(d4)
    assert not os.path.isdir(d2) and not os.path.isdir(d3)
    assert sorted(removed) == [e2, e3]

    pin.release()
    store.gc(retain=1)
    assert not os.path.isdir(d1)
    assert read_current(root) == (e4, d4)
    assert store.epochs() == [e4]


def test_epoch_pin_outlives_gc(tmp_path):
    """A serving pin keeps the old epoch's database readable even after
    GC unlinks its directory — the no-closed-mmap guarantee."""
    root = str(tmp_path / "live")
    store = SnapshotStore(root)
    state = IngestState(config=_serial_cfg())
    state.append(_write_profiles(tmp_path, 3))
    e1, d1 = store.publish(state.write_database)

    switcher = EpochSwitcher(root)
    assert switcher.epoch == e1
    pin = switcher.acquire()  # an in-flight batch holds this

    state.append(_write_profiles(tmp_path, 2, start=3))
    e2, _ = store.publish(state.write_database)
    store.gc(retain=1)
    assert not os.path.isdir(d1)  # old epoch gone from disk

    assert switcher.poll() is True
    assert switcher.epoch == e2
    # the pinned handle still answers from the unlinked files
    res = QueryServer(pin.db).serve_one(QueryRequest(op="profile", pid=1),
                                        db=pin.db)
    assert not isinstance(res, QueryError)
    assert pin.db.n_profiles == 3 and switcher.db.n_profiles == 5
    pin.release()
    switcher.close()


# ---------------------------------------------------------------------------
# HTTP ingest endpoint
# ---------------------------------------------------------------------------

def test_ingest_http_error_paths(tmp_path):
    blob = open(_write_profiles(tmp_path, 1)[0], "rb").read()
    root = str(tmp_path / "live")
    with IngestHTTPServer(root, config=_serial_cfg(), max_pending=2,
                          max_body_bytes=1 << 16) as ing:
        host, port = ing.address
        with IngestClient(host, port) as c:
            # publish with nothing ingested is a structural 400
            with pytest.raises(TransportError) as ei:
                c.publish()
            assert ei.value.status == 400

            with pytest.raises(TransportError) as ei:
                c.upload(b"not an rprf blob")
            assert ei.value.status == 400

            with pytest.raises(TransportError) as ei:
                c._roundtrip("POST", "/v1/ingest", {"profiles": []})
            assert ei.value.status == 400

            with pytest.raises(TransportError) as ei:
                c.upload(b"RPRF" + b"\0" * (1 << 16))
            assert ei.value.status == 413

            # backpressure: freeze the merger, fill the spool bound
            ing.pause()
            c.upload(blob)
            c.upload(blob)
            with pytest.raises(ServerOverloaded) as oi:
                c.upload(blob)
            assert oi.value.retry_after_s > 0

            # a retrying client rides the 429 out once the merger resumes
            timer = threading.Timer(0.2, ing.resume)
            timer.start()
            try:
                res = c.upload_with_retry([blob])
            finally:
                timer.cancel()
            assert res["accepted"] == 1

            pub = c.publish()
            assert pub["epoch"] == 1
            m = c.metrics()
            assert m["rejected_overload"] >= 1
            assert m["profiles_merged"] == 3
            assert m["epochs_published"] == 1
            with Database(os.path.join(root, pub["dir"])) as db:
                assert db.n_profiles == 3


def test_ingest_spool_recovers_after_restart(tmp_path):
    paths = _write_profiles(tmp_path, 3)
    blobs = [open(p, "rb").read() for p in paths]
    root = str(tmp_path / "live")

    srv = IngestHTTPServer(root, config=_serial_cfg())
    srv.start()
    srv.pause()  # accepted but never merged: stays in the spool
    host, port = srv.address
    with IngestClient(host, port) as c:
        c.upload_many(blobs)
    srv.stop()

    # a new server over the same root re-enqueues the spool in order
    with IngestHTTPServer(root, config=_serial_cfg()) as srv2:
        host, port = srv2.address
        with IngestClient(host, port) as c:
            pub = c.publish()
    one = tmp_path / "one"
    StreamingAggregator(one, _serial_cfg()).run(paths)
    edir = os.path.join(root, pub["dir"])
    for name in DB_FILES:
        assert filecmp.cmp(os.path.join(edir, name), str(one / name),
                           shallow=False)


# ---------------------------------------------------------------------------
# live serving across epoch transitions
# ---------------------------------------------------------------------------

def _epoch_answers(root, epoch, reqs):
    with Database(os.path.join(root, epoch_dirname(epoch))) as db:
        server = QueryServer(db)
        return [result_to_wire(server.serve_one(r)) for r in reqs]


def test_follow_single_process(tmp_path):
    blobs = [open(p, "rb").read() for p in _write_profiles(tmp_path, 6)]
    root = str(tmp_path / "live")
    reqs = [QueryRequest(op="topk", metric=1, k=64, inclusive=True),
            QueryRequest(op="profile", pid=0)]
    with IngestHTTPServer(root, config=_serial_cfg()) as ing:
        ihost, iport = ing.address
        with IngestClient(ihost, iport) as ic:
            ic.upload_many(blobs[:3])
            e1 = ic.publish()["epoch"]
            with QueryHTTPServer(root, follow=True, poll_ms=20,
                                 warm_bytes=0) as srv:
                qhost, qport = srv.address
                with QueryClient(qhost, qport) as qc:
                    assert qc.health()["epoch"] == e1
                    got = [result_to_wire(r) for r in qc.batch(reqs)]
                    assert got == _epoch_answers(root, e1, reqs)

                    ic.upload_many(blobs[3:])
                    e2 = ic.publish()["epoch"]
                    deadline = time.monotonic() + 15
                    while qc.health().get("epoch") != e2:
                        assert time.monotonic() < deadline, \
                            "follower never saw the new epoch"
                        time.sleep(0.02)
                    got = [result_to_wire(r) for r in qc.batch(reqs)]
                    assert got == _epoch_answers(root, e2, reqs)
                    m = qc.metrics()
                    assert m["epoch"]["transitions"] == 2
                    assert m["epoch"]["follow_errors"] == 0


def test_follow_sharded_no_mixed_epoch_replies(tmp_path):
    """A sharded follower crosses >= 2 epoch transitions under continuous
    query fire; every batched reply matches exactly one epoch's answers
    in full — never a mix — and no worker was restarted to get there."""
    blobs = [open(p, "rb").read() for p in _write_profiles(tmp_path, 9)]
    root = str(tmp_path / "live")
    # scatter ops: their answers span every shard, so a torn epoch switch
    # would be visible as a reply matching no single epoch
    reqs = [QueryRequest(op="topk", metric=1, k=256, inclusive=True),
            QueryRequest(op="threshold", metric=1, inclusive=True,
                         params={"min_value": 0.0})]
    expected: dict[int, list] = {}
    with IngestHTTPServer(root, config=_serial_cfg(), merge_batch=4) as ing:
        ihost, iport = ing.address
        with IngestClient(ihost, iport) as ic:
            ic.upload_many(blobs[:3])
            e1 = ic.publish()["epoch"]
            expected[e1] = _epoch_answers(root, e1, reqs)
            with QueryHTTPServer(root, follow=True, poll_ms=20, shards=2,
                                 warm_bytes=0) as srv:
                qhost, qport = srv.address
                stop = threading.Event()
                batches: list[list] = []
                errors: list[Exception] = []

                def fire():
                    with QueryClient(qhost, qport) as qc2:
                        while not stop.is_set():
                            try:
                                res = qc2.batch(reqs)
                            except Exception as e:       # noqa: BLE001
                                errors.append(e)
                                return
                            batches.append(
                                [result_to_wire(r) for r in res])

                thread = threading.Thread(target=fire, daemon=True)
                thread.start()
                with QueryClient(qhost, qport) as qc:
                    for lo, hi in ((3, 6), (6, 9)):
                        ic.upload_many(blobs[lo:hi])
                        epoch = ic.publish()["epoch"]
                        expected[epoch] = _epoch_answers(root, epoch, reqs)
                        deadline = time.monotonic() + 20
                        while qc.health().get("epoch") != epoch:
                            assert time.monotonic() < deadline, \
                                "follower never switched"
                            time.sleep(0.02)
                        time.sleep(0.1)  # observe post-switch replies
                    stop.set()
                    thread.join(timeout=15)
                    metrics = qc.metrics()
                assert not errors, errors[:1]
                assert metrics["epoch"]["transitions"] == 3  # open + 2
                assert metrics["shards"]["reopens"] == 2
                assert metrics["shards"]["respawns"] == 0

                assert batches, "query thread never completed a batch"
                seen = set()
                for got in batches:
                    owners = [e for e, ans in expected.items()
                              if got == ans]
                    assert owners, "reply mixes epochs (or matches none)"
                    seen.add(owners[0])
                # replies were observed from more than one epoch, so the
                # single-epoch property was exercised across a transition
                assert len(seen) >= 2


def test_spool_checksum_quarantines_corrupt_entries(tmp_path):
    """Spool entries carry a crc32 in their filename; a restart
    re-enqueues only entries whose checksum (or, for legacy names, RPRF
    magic) still holds and quarantines the rest instead of poisoning a
    merge batch."""
    import glob

    from repro.ingest.server import (QUARANTINE_DIR, SPOOL_DIR,
                                     spool_entry_name, spool_entry_ok)
    paths = _write_profiles(tmp_path, 4)
    blobs = [open(p, "rb").read() for p in paths]
    root = str(tmp_path / "live")

    srv = IngestHTTPServer(root, config=_serial_cfg())
    srv.start()
    srv.pause()  # accepted but never merged: stays in the spool
    host, port = srv.address
    with IngestClient(host, port) as c:
        c.upload_many(blobs[:3])
    srv.stop()

    spool = os.path.join(root, SPOOL_DIR)
    entries = sorted(os.listdir(spool))
    assert len(entries) == 3
    assert all(spool_entry_ok(os.path.join(spool, n), n) for n in entries)
    assert entries[1] == spool_entry_name(1, blobs[1])
    # flip a byte in the middle entry: its filename crc no longer matches
    mid = os.path.join(spool, entries[1])
    data = bytearray(open(mid, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(mid, "wb").write(bytes(data))
    # a pre-checksum (legacy, two-part name) entry that is still valid...
    open(os.path.join(spool, "000000000098.rprf"), "wb").write(blobs[3])
    # ...and legacy junk that never was a profile
    open(os.path.join(spool, "000000000099.rprf"), "wb").write(b"not rprf")

    # recovery runs in the constructor; pausing before start keeps the
    # merger from draining the survivors before they are counted
    srv2 = IngestHTTPServer(root, config=_serial_cfg())
    srv2.pause()
    with srv2:
        host, port = srv2.address
        with IngestClient(host, port) as c:
            m = c.metrics()
            assert m["spool_quarantined"] == 2
            assert m["pending"] == 3
            srv2.resume()
            pub = c.publish()
    qdir = os.path.join(spool, QUARANTINE_DIR)
    assert sorted(os.listdir(qdir)) == [entries[1], "000000000099.rprf"]
    # the survivors merged in seq order, byte-identical to a one-shot
    # over exactly those profiles
    one = tmp_path / "one"
    StreamingAggregator(one, _serial_cfg()).run(
        [paths[0], paths[2], paths[3]])
    edir = os.path.join(root, pub["dir"])
    for name in DB_FILES:
        assert filecmp.cmp(os.path.join(edir, name), str(one / name),
                           shallow=False)
    assert not glob.glob(os.path.join(spool, "*.rprf"))


def test_replicated_reopen_races_worker_death_no_mixed_epochs(tmp_path):
    """Satellite of the replication tentpole: a sharded follower with
    R=2 ownership crosses epoch transitions while workers are SIGKILLed
    right as each epoch publishes — the reopen/respawn/failover machinery
    interleaves, yet every batched reply still matches exactly one
    epoch's answers in full."""
    import signal as _signal
    if not hasattr(_signal, "SIGKILL"):
        pytest.skip("POSIX only")
    blobs = [open(p, "rb").read() for p in _write_profiles(tmp_path, 9)]
    root = str(tmp_path / "live")
    reqs = [QueryRequest(op="topk", metric=1, k=256, inclusive=True),
            QueryRequest(op="threshold", metric=1, inclusive=True,
                         params={"min_value": 0.0})]
    expected: dict[int, list] = {}
    with IngestHTTPServer(root, config=_serial_cfg(), merge_batch=4) as ing:
        ihost, iport = ing.address
        with IngestClient(ihost, iport) as ic:
            ic.upload_many(blobs[:3])
            e1 = ic.publish()["epoch"]
            expected[e1] = _epoch_answers(root, e1, reqs)
            with QueryHTTPServer(root, follow=True, poll_ms=20, shards=3,
                                 replicas=2, warm_bytes=0) as srv:
                qhost, qport = srv.address
                stop = threading.Event()
                batches: list[list] = []
                errors: list[Exception] = []

                def fire():
                    with QueryClient(qhost, qport) as qc2:
                        while not stop.is_set():
                            try:
                                res = qc2.batch(reqs)
                            except Exception as e:       # noqa: BLE001
                                errors.append(e)
                                return
                            batches.append(
                                [result_to_wire(r) for r in res])

                thread = threading.Thread(target=fire, daemon=True)
                thread.start()
                with QueryClient(qhost, qport) as qc:
                    for n, (lo, hi) in enumerate(((3, 6), (6, 9))):
                        ic.upload_many(blobs[lo:hi])
                        epoch = ic.publish()["epoch"]
                        # land a kill in the follower's reopen window
                        pid = srv.sharded.worker_pids()[n % 3]
                        os.kill(pid, _signal.SIGKILL)
                        expected[epoch] = _epoch_answers(root, epoch, reqs)
                        deadline = time.monotonic() + 30
                        while qc.health().get("epoch") != epoch:
                            assert time.monotonic() < deadline, \
                                "follower never switched"
                            time.sleep(0.02)
                        time.sleep(0.1)  # observe post-switch replies
                    stop.set()
                    thread.join(timeout=15)
                    metrics = qc.metrics()
                assert not errors, errors[:1]
                assert metrics["epoch"]["transitions"] == 3  # open + 2
                assert metrics["shards"]["reopens"] == 2
                deadline = time.monotonic() + 20
                while srv.sharded.metrics()["respawns"] < 2 and \
                        time.monotonic() < deadline:
                    time.sleep(0.05)
                assert srv.sharded.metrics()["respawns"] >= 2

                assert batches, "query thread never completed a batch"
                for got in batches:
                    owners = [e for e, ans in expected.items()
                              if got == ans]
                    assert owners, "reply mixes epochs (or matches none)"
