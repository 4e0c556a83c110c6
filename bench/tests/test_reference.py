"""The plain reference agrees with ``analyze --compute cpu`` (the numpy
path, float64) on a small fleet of each configuration, with and without
binary structure, and the comparison sees a database that differs from
it."""
import contextlib
import io
import json
from pathlib import Path

import numpy as np
import pytest

from bench import reference
from bench.dbread import read_database
from bench.fleets import (KIND_LINE, KIND_LOOP, KIND_MODULE, KIND_OP,
                          KIND_PHASE, Fleet, FleetShape, Profile, Structure,
                          Tree, encode_profile, write_fleet)

ROOT = Path(__file__).resolve().parents[2]


def analyze_cpu(fleet, out):
    from repro.launch import analyze

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        analyze.main([*fleet.paths, "--out", str(out), "--executor", "threads",
                      "--workers", "4", "--compute", "cpu", "--no-traces"])
    s = json.loads(buf.getvalue())
    return read_database(s["pms"], s["cms"])


@pytest.fixture(scope="module",
                params=["tiny-pelec", "tiny-amg", "tiny-pelec-routes"])
def case(request, tmp_path_factory):
    conf = json.loads((ROOT / f"bench/tests/configs/{request.param}.json")
                      .read_text())
    tmp = tmp_path_factory.mktemp(request.param)
    fleet = write_fleet(FleetShape.from_config(conf), 2**40 + 3, str(tmp / "f"))
    return fleet, analyze_cpu(fleet, tmp / "db")


def test_reference_agrees_with_cpu_path(case):
    fleet, db = case
    numbers = reference.compare(reference.build(fleet), db)
    assert numbers["tree_mismatch"] == 0
    assert numbers["order_errors"] == 0
    assert numbers["count_excess"] == 0
    for k in ("pms_gap_u", "stats_gap_u", "cms_gap_u"):
        assert numbers[k] < 1e-3, (k, numbers[k])   # float64 rounding only


def test_comparison_sees_a_changed_value(case):
    fleet, db = case
    c, m, v = db.planes[1]
    db.planes[1] = (c, m, np.where(v == v.max(), v * 1.001, v))
    numbers = reference.compare(reference.build(fleet), db)
    assert numbers["pms_gap_u"] > 1000
    db.planes[1] = (c, m, v)


def test_comparison_sees_a_changed_std(case):
    fleet, db = case
    std = db.stats["std"]
    db.stats["std"] = np.where(std == std.max(), std * 1.001, std)
    numbers = reference.compare(reference.build(fleet), db)
    assert numbers["stats_gap_u"] > 100
    db.stats["std"] = std


def test_comparison_sees_a_missing_plane_and_a_wrong_tree(case):
    fleet, db = case
    ref = reference.build(fleet)
    planes = db.planes
    db.planes = planes[:-1]
    assert reference.compare(ref, db)["pms_gap_u"] >= 2**24 * 0.5
    db.planes = planes
    names = db.names
    db.names = names[:-1] + ["renamed"]
    assert reference.compare(ref, db)["tree_mismatch"] == 1
    db.names = names


def test_two_routes_split_one_to_three(tmp_path):
    """One op reached through two routes that share an inlined function,
    weighted 1 : 3, with a line of its own below it; the program reads the
    same profile and structure and gives the same database."""
    tree = Tree(np.array([-1, 0, 1, 2, 3]),
                np.array([0, KIND_PHASE, KIND_MODULE, KIND_OP, KIND_LINE],
                         np.uint8), ["<root>", "main", "mod0", "k", "l"])
    ops = {"k": [{"path": [[KIND_MODULE, "inl"], [KIND_LOOP, "loopA"]],
                  "weight": 1},
                 {"path": [[KIND_MODULE, "inl"], [KIND_LOOP, "loopB"]],
                  "weight": 3}]}
    v, u = 8.0, 2.0
    prof = Profile(tree, np.array([3, 4]), np.array([0, 0]), np.array([v, u]),
                   {"rank": 0}, Structure("hand.gpu", ops))
    ref = reference.build(Fleet(FleetShape("hand", 1, 5, 1, 0, 1.0, 1.0),
                                [prof], []))
    assert ref.names == ["<root>", "main", "mod0", "inl", "loopA", "k",
                         "loopB", "k", "k@superposition", "l"]
    assert ref.parent.tolist() == [-1, 0, 1, 2, 3, 4, 3, 6, 2, 8]
    assert ref.kind.tolist() == [0, KIND_PHASE, KIND_MODULE, KIND_MODULE,
                                 KIND_LOOP, KIND_OP, KIND_LOOP, KIND_OP,
                                 reference.KIND_ROUTE, KIND_LINE]
    got = {(int(k >> 16) & 0xFFFFFF, int(k & 0xFFFF)): float(x)
           for k, x in zip(ref.keys, ref.vals)}
    inc = reference.INCLUSIVE_BIT
    assert got == {(5, 0): v / 4, (7, 0): 3 * v / 4, (9, 0): u,
                   (5, inc): v / 4, (4, inc): v / 4, (7, inc): 3 * v / 4,
                   (6, inc): 3 * v / 4, (3, inc): v, (9, inc): u, (8, inc): u,
                   (2, inc): v + u, (1, inc): v + u, (0, inc): v + u}

    struct = tmp_path / "hand.gpu.struct.json"
    struct.write_bytes(prof.structure.encode())
    path = tmp_path / "hand.rprf"
    path.write_bytes(encode_profile(prof, "hand", 1, [str(struct)]))
    fleet = Fleet(FleetShape("hand", 1, 5, 1, 0, 1.0, 1.0), [prof], [str(path)])
    numbers = reference.compare(ref, analyze_cpu(fleet, tmp_path / "db"))
    assert numbers["tree_mismatch"] == 0 and numbers["count_excess"] == 0
    assert numbers["pms_gap_u"] == 0 and numbers["cms_gap_u"] == 0
