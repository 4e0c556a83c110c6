"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Usage::

    PYTHONPATH=src python -m benchmarks.run [--only table4] \
        [--executor processes] [--tiny]

``--executor`` / ``--tiny`` are forwarded to every suite whose ``run``
accepts them (currently table4); other suites ignore the knobs.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--executor", default=None,
                    help="aggregation backend for executor-aware suites "
                         "(serial | threads | processes)")
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized workloads for suites that support it")
    args = ap.parse_args()

    from benchmarks import (fig6_breakdown, kernels_bench, query_latency,
                            serve_load, table1_measurement, table2_analysis,
                            table4_agg_time, table5_glb)
    suites = {
        "table1": table1_measurement.run,
        "table2": table2_analysis.run,
        "table4": table4_agg_time.run,
        "table5": table5_glb.run,
        "fig6": fig6_breakdown.run,
        "query": query_latency.run,
        "kernels": kernels_bench.run,
        "serve": serve_load.run,
    }
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if args.only and args.only != name:
            continue
        params = inspect.signature(fn).parameters
        kwargs = {}
        if args.executor is not None and "executor" in params:
            kwargs["executor"] = args.executor
        if args.tiny and "tiny" in params:
            kwargs["tiny"] = True
        t0 = time.perf_counter()
        try:
            fn(out=print, **kwargs)
        except Exception as e:
            # emit a parse-friendly marker for the CSV consumer, then abort:
            # CI keys off the nonzero exit
            print(f"{name}.ERROR,0,{type(e).__name__}:{e}", file=sys.stdout)
            raise
        print(f"{name}.total,{(time.perf_counter()-t0)*1e6:.0f},",
              flush=True)


if __name__ == "__main__":
    main()
