"""The fleet generator keeps the paper row's shape, writes profiles the
program reads back as they were drawn, and draws binary structure only
where a configuration asks for it."""
import hashlib
import json
import os
from pathlib import Path

import numpy as np
import pytest

from bench import reference
from bench.fleets import (KIND_LOOP, KIND_MODULE, KIND_OP, MAX_WEIGHT,
                          FleetShape, make_fleet, write_fleet)

ROOT = Path(__file__).resolve().parents[2]
ROUTES = ROOT / "bench/tests/configs/tiny-pelec-routes.json"

# md5 of every .rprf of a small fleet, in profile order, as the generator
# wrote them before a configuration could carry structure
PLAIN_MD5 = {("pelec-1x82", 3): "b83ce981460039362249a501580667dd",
             ("pelec-1x82", 2**33 + 17): "92b924a98b1be5e0667a442e5d8fdb39",
             ("amg2013-1", 3): "ea8399969315e4ab93b5e7023c45577e",
             ("amg2013-1", 2**33 + 17): "720f41e5e3ac6a80af8c7e25038f4187"}


def shape_of(name: str, **over) -> FleetShape:
    conf = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    conf.update(over)
    return FleetShape.from_config(conf)


def routes_shape(**over) -> FleetShape:
    conf = json.loads(ROUTES.read_text())
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


@pytest.mark.parametrize("name,seed", sorted(PLAIN_MD5))
def test_plain_fleets_write_the_same_bytes(tmp_path, name, seed):
    conf = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    shape = shape_of(name, n_profiles=4, n_ctx=400,
                     n_private=min(conf["n_private"], 40))
    fleet = write_fleet(shape, seed, str(tmp_path))
    h = hashlib.md5()
    for path in fleet.paths:
        h.update(Path(path).read_bytes())
    assert h.hexdigest() == PLAIN_MD5[name, seed]
    assert all(p.structure is None for p in fleet.profiles)
    assert sorted(os.listdir(tmp_path)) == sorted(
        os.path.basename(p) for p in fleet.paths)


def test_structure_files_are_a_function_of_the_seed(tmp_path):
    def files(seed, where):
        write_fleet(routes_shape(), seed, str(tmp_path / where))
        return {f: (tmp_path / where / f).read_bytes()
                for f in sorted(os.listdir(tmp_path / where))
                if f.endswith(".struct.json")}

    a, b, c = files(2**35 + 1, "a"), files(2**35 + 1, "b"), files(9, "c")
    assert list(a) == ["tiny-pelec-routes.cpu.struct.json",
                       "tiny-pelec-routes.gpu.struct.json"]
    assert a == b
    assert list(c) == list(a) and all(c[f] != a[f] for f in a)


def test_structured_profiles_read_back_with_their_file_paths(tmp_path):
    from repro.core.lexical import StructureInfo
    from repro.core.sparse import MeasurementProfile

    shape = routes_shape()
    fleet = write_fleet(shape, 2**33 + 5, str(tmp_path))
    for p, (prof, path) in enumerate(zip(fleet.profiles, fleet.paths)):
        got = MeasurementProfile.load(path)
        binary = "gpu" if shape.is_gpu(p) else "cpu"
        assert prof.structure.binary == f"{shape.name}.{binary}"
        assert got.file_paths == [
            str(tmp_path / f"{shape.name}.{binary}.struct.json")]
        info = StructureInfo.load(got.file_paths[0])
        assert info.binary == prof.structure.binary
        assert info.ops == prof.structure.ops
        assert got.tree.parent == prof.tree.parent.tolist()
        rows, mids, vals = got.metrics.triplets()
        assert np.array_equal(rows, prof.ctx) and np.array_equal(vals, prof.val)


@pytest.mark.parametrize("scopes,share,routes", [(2, 0.3, 3), (3, 0.5, 2),
                                                 (1, 0.0, 0)])
def test_routes_come_out_as_configured(scopes, share, routes):
    shape = routes_shape(struct_scopes=scopes, routed_share=share,
                         n_routes=routes)
    profiles = make_fleet(shape, 2**31 + 11)
    tree = profiles[0].tree
    ops = [n for n, k in zip(tree.names, tree.kind.tolist()) if k == KIND_OP]
    cpu, gpu = profiles[0].structure, profiles[1].structure
    assert all(p.structure is (gpu if shape.is_gpu(i) else cpu)
               for i, p in enumerate(profiles))
    kinds = [KIND_MODULE if j % 2 == 0 else KIND_LOOP for j in range(scopes)]
    for s in (cpu, gpu):
        assert sorted(s.ops) == sorted(ops)
        for entries in s.ops.values():
            assert all([k for k, _ in e["path"]] == kinds for e in entries)
    assert all(len(r) == 1 and r[0]["weight"] == 1 for r in cpu.ops.values())
    routed = {op: r for op, r in gpu.ops.items() if len(r) > 1}
    assert len(routed) == int(len(ops) * share)
    for entries in routed.values():
        assert len(entries) == routes
        assert len({e["path"][0][1] for e in entries}) == routes
        assert all(isinstance(e["weight"], int)
                   and 1 <= e["weight"] <= MAX_WEIGHT for e in entries)
    assert all(r[0]["weight"] == 1 for op, r in gpu.ops.items()
               if op not in routed)


def test_routes_need_scopes_and_two_routes():
    with pytest.raises(ValueError):
        routes_shape(struct_scopes=0)
    with pytest.raises(ValueError):
        routes_shape(n_routes=1)
