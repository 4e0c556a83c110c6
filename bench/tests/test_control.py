"""The controls, the reference computed at ``high`` (three bfloat16 passes)
or in bfloat16 and put in the program's place, come out not correct on
every seed; the float64 reference read against itself comes out correct."""
import json

import pytest

from bench import reference
from bench.control import control_numbers
from bench.fleets import Fleet, FleetShape, make_fleet

from conftest import TINY

# the number each control fails by the widest margin
FAILS = {"high": "excl_gap_u", "bfloat16": "pms_gap_u"}


@pytest.mark.parametrize("precision", sorted(FAILS))
@pytest.mark.parametrize("name", sorted(TINY))
@pytest.mark.parametrize("seed", [1, 2**35 + 7, 2**31 + 2])
def test_control_fails_the_limits(name, seed, precision):
    conf = json.loads(TINY[name].read_text())
    shape = FleetShape.from_config(conf)
    fleet = Fleet(shape, make_fleet(shape, seed), [])
    numbers = control_numbers(fleet, precision)
    assert not reference.verdict(numbers, conf["limits"])
    key = FAILS[precision]
    assert numbers[key] > 4 * conf["limits"][key]


@pytest.mark.parametrize("name", sorted(TINY))
def test_reference_against_itself_is_correct(name):
    conf = json.loads(TINY[name].read_text())
    shape = FleetShape.from_config(conf)
    ref = reference.build(Fleet(shape, make_fleet(shape, 2**33 + 3), []))
    same = reference.compare(ref, reference.control_database(ref))
    same["repeat_mismatch"] = 0.0
    assert reference.verdict(same, conf["limits"])
    assert same["excl_gap_u"] == 0 and same["pms_gap_u"] == 0
