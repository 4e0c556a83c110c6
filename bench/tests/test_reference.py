"""The plain reference agrees with ``analyze --compute cpu`` (the numpy
path, float64) on a small fleet of each configuration, and the comparison
sees a database that differs from it."""
import contextlib
import io
import json
from pathlib import Path

import numpy as np
import pytest

from bench import reference
from bench.dbread import read_database
from bench.fleets import FleetShape, write_fleet

ROOT = Path(__file__).resolve().parents[2]


def analyze_cpu(fleet, out):
    from repro.launch import analyze

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        analyze.main([*fleet.paths, "--out", str(out), "--executor", "threads",
                      "--workers", "4", "--compute", "cpu", "--no-traces"])
    s = json.loads(buf.getvalue())
    return read_database(s["pms"], s["cms"])


@pytest.fixture(scope="module", params=["tiny-pelec", "tiny-amg"])
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
