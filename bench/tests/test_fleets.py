"""The fleet generator keeps the paper row's shape, and writes profiles the
program reads back as they were drawn."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import reference
from bench.fleets import FleetShape, make_fleet, write_fleet

ROOT = Path(__file__).resolve().parents[2]


def shape_of(name: str, **over) -> FleetShape:
    conf = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    conf.update(over)
    return FleetShape.from_config(conf)


def test_pelec_profiles_and_unified_tree():
    shape = shape_of("pelec-1x82", n_profiles=3)
    profiles = make_fleet(shape, 2**33 + 1)
    for p, prof in enumerate(profiles):
        live = np.unique(prof.ctx)
        assert live.size == int(1200 * 0.04) + 700
        assert prof.val.size == live.size          # one metric a context
        if p % 2:
            assert prof.mid.min() >= 1 and prof.mid.max() <= 82
        else:
            assert np.all(prof.mid == 0)
        assert np.all(prof.val > 0)
    parent, _, _, maps = reference.unify([p.tree for p in profiles])
    assert parent.size == 1201 + 3 * 701


def test_amg_profiles_and_unified_tree():
    shape = shape_of("amg2013-1", n_profiles=2, n_ctx=4000)
    profiles = make_fleet(shape, 5)
    for prof in profiles:
        assert prof.val.size == int(4000 * 0.691)
        assert np.unique(prof.ctx).size == prof.val.size
        assert np.all(prof.mid == 0)
    parent, _, _, _ = reference.unify([p.tree for p in profiles])
    assert parent.size == 4000


def test_several_metrics_a_context_are_distinct():
    shape = FleetShape("wide", 2, 400, 2, 8, 0.5, 0.5)
    prof = make_fleet(shape, 3)[1]
    k = shape.metrics_per_context(8)
    assert k == 5
    for c in np.unique(prof.ctx):
        mids = prof.mid[prof.ctx == c]
        assert mids.size == k and np.unique(mids).size == k
        assert mids.min() >= 2


def test_same_seed_same_fleet():
    shape = shape_of("pelec-1x82", n_profiles=2, n_private=50)
    a, b, c = (make_fleet(shape, s) for s in (7, 7, 8))
    assert all(np.array_equal(x.val, y.val) for x, y in zip(a, b))
    assert not all(np.array_equal(x.ctx, y.ctx) for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["pelec-1x82", "amg2013-1"])
def test_written_profiles_read_back(tmp_path, name):
    from repro.core.sparse import MeasurementProfile

    shape = shape_of(name, n_profiles=2, n_ctx=500, n_private=min(
        json.loads((ROOT / f"bench/configs/{name}.json").read_text())
        ["n_private"], 30))
    fleet = write_fleet(shape, 11, str(tmp_path))
    for prof, path in zip(fleet.profiles, fleet.paths):
        got = MeasurementProfile.load(path)
        assert got.tree.parent == prof.tree.parent.tolist()
        assert got.tree.kind == prof.tree.kind.tolist()
        assert [got.tree.name_of(i) for i in range(len(got.tree))] == prof.tree.names
        rows, mids, vals = got.metrics.triplets()
        assert np.array_equal(rows, prof.ctx) and np.array_equal(mids, prof.mid)
        assert np.array_equal(vals, prof.val)
        assert got.identity == prof.identity
