import contextlib
import os
from multiprocessing import shared_memory

import numpy as np
import pytest

from repro.core.aggregate import AggregationConfig, StreamingAggregator
from repro.core.cct import KIND_LINE, KIND_MODULE, KIND_OP, KIND_PHASE, ContextTree
from repro.core.cms import _gather_group_heap
from repro.core.propagate import propagate_inclusive, redistribute_placeholders
from repro.core.sparse import MeasurementProfile, SparseMetrics, Trace
from repro.runtime import shm as shm_mod


def random_tree(rng: np.random.Generator, n_nodes: int) -> ContextTree:
    """Random program-structure tree with realistic kinds."""
    t = ContextTree()
    kinds = [KIND_PHASE, KIND_MODULE, KIND_MODULE, KIND_OP, KIND_LINE]
    ids = [0]
    for i in range(n_nodes):
        parent = int(rng.choice(ids))
        k = kinds[min(len(kinds) - 1, int(rng.integers(0, len(kinds))))]
        ids.append(t.child(parent, k, f"n{i % max(n_nodes // 4, 1)}"))
    return t


def random_sparse(rng: np.random.Generator, n_ctx: int, n_metrics: int,
                  density: float = 0.1) -> SparseMetrics:
    n = max(int(n_ctx * n_metrics * density), 1)
    ctx = rng.integers(0, n_ctx, n)
    mid = rng.integers(0, n_metrics, n)
    val = rng.uniform(0.5, 10.0, n)
    return SparseMetrics.from_triplets(ctx, mid, val)


def make_profile(rng: np.random.Generator, n_nodes=50, n_metrics=8, density=0.2,
                 n_trace=20, identity=None) -> MeasurementProfile:
    tree = random_tree(rng, n_nodes)
    sm = random_sparse(rng, len(tree.parent), n_metrics, density)
    trace = Trace(
        np.sort(rng.uniform(0, 1, n_trace)),
        rng.integers(0, len(tree.parent), n_trace).astype(np.uint32),
    )
    return MeasurementProfile(
        environment={"app": "test", "metrics": n_metrics},
        identity=identity or {"rank": 0, "stream": 0, "kind": "device"},
        file_paths=["bin/test"],
        tree=tree, trace=trace, metrics=sm,
    )


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# ---------------------------------------------------------------------------
# the references the engine is checked against
# ---------------------------------------------------------------------------

def legacy_transform(metrics, remap, routes, parent, end, *,
                     keep_exclusive=True, device=None, timer=None):
    """The three-pass reference chain with ``fused_transform``'s signature:
    ``remap_contexts``, then ``redistribute_placeholders``, then
    ``propagate_inclusive``."""
    assert device is None, "the reference chain has no device path"
    sm = metrics.remap_contexts(np.asarray(remap, dtype=np.int64))
    if routes:
        sm = redistribute_placeholders(sm, routes)
    return propagate_inclusive(sm, np.arange(end.size), end,
                               keep_exclusive=keep_exclusive)


@contextlib.contextmanager
def reference_engine():
    """The engine with its references in place of the paths it runs: the
    three-pass chain for ``fused_transform`` and the heap merge
    (paper §4.3.2) for the vectorised CMS gather."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("repro.core.aggregate.fused_transform", legacy_transform)
        mp.setattr("repro.core.cms._gather_group_vectorized",
                   _gather_group_heap)
        yield


def reference_run(out_dir, paths, **config):
    """The database the references write, serially and in-process."""
    with reference_engine():
        return StreamingAggregator(out_dir, AggregationConfig(
            executor="serial", **config)).run(paths)


# ---------------------------------------------------------------------------
# shared-memory leak checks
# ---------------------------------------------------------------------------

@pytest.fixture
def own_shm(monkeypatch, tmp_path):
    """Record each shared-memory segment that ``repro.runtime.shm`` creates
    during the test, in this process or in a worker forked from it, and
    return ``created()``, their names in order of creation.

    A leak check looks these names up (:func:`shm_alive`): counting every
    ``psm_*`` entry in ``/dev/shm`` would also count the segments that
    other test processes create and unlink at the same time."""
    log = tmp_path / "shm_created.log"

    class Recording(shared_memory.SharedMemory):
        def __init__(self, name=None, create=False, size=0, **kw):
            super().__init__(name=name, create=create, size=size, **kw)
            if create:
                with open(log, "a") as f:
                    f.write(self.name + "\n")

    class SharedMemoryModule:
        SharedMemory = Recording

    monkeypatch.setattr(shm_mod, "shared_memory", SharedMemoryModule)
    return lambda: log.read_text().split() if log.exists() else []


def shm_alive(names) -> set:
    """The segments among ``names`` that still exist."""
    return {n for n in names if os.path.exists(os.path.join("/dev/shm", n))}
