#!/usr/bin/env python3
"""The controls of the comparison that decides ``correct``: the plain
reference computed in a precision below the float32 that the device path
keeps at ``Precision.HIGHEST``, put in the program's place.  ``high`` (three
bfloat16 passes) is the step a kernel could take; ``bfloat16`` the one below
float32 itself.

    python3 bench/control.py --workload pelec-1x82.analyze --seeds 1,2,3

For each seed and precision it draws the cell's fleet at the cell's own
size, builds the reference in float64 and in that precision, reads the
narrower one as a database against the float64 one, and prints the numbers
beside the cell's limits (one JSON line per seed and precision, then the
least of each number per precision).  Each control has to come out not
correct.  It needs no chip; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


PRECISIONS = ("high", "bfloat16")


def control_numbers(fleet, precision: str, ref=None) -> dict:
    from bench import reference

    ref = ref or reference.build(fleet)
    low = reference.build(fleet, precision)
    numbers = reference.compare(ref, reference.control_database(low))
    numbers["repeat_mismatch"] = 0.0    # one database, read once
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--benchmark", default=str(ROOT / "BENCHMARK.json"))
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import reference
    from bench.fleets import Fleet, FleetShape, make_fleet
    from bench.run import load_cell

    cell = load_cell(Path(args.benchmark), args.workload)
    shape = FleetShape.from_config(cell.config)
    limits = cell.config["limits"]
    least: dict = {p: {} for p in PRECISIONS}
    verdicts = []
    for seed in (int(s) for s in args.seeds.split(",")):
        fleet = Fleet(shape, make_fleet(shape, seed), [])
        ref = reference.build(fleet)
        for precision in PRECISIONS:
            numbers = control_numbers(fleet, precision, ref)
            verdicts.append(reference.verdict(numbers, limits))
            print(json.dumps({"seed": seed, "precision": precision,
                              "correct": verdicts[-1], **numbers}), flush=True)
            for k, v in numbers.items():
                if v is not None:
                    least[precision][k] = min(least[precision].get(k, v), v)
    print(json.dumps({"workload": args.workload, "any_correct": any(verdicts),
                      "least": least, "limits": limits}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
