"""Thread-seconds of phase 1's uniquing: each profile's tree expanded with
its binary's lexical scopes and placeholders and merged into the unified
tree, under the uniquing lock: ``analyze``'s ``timings["phase1/unify"]``
(a program span's self time, summed over threads), mean over the run's
complete analyses."""
from bench.spans import timings_mean


def read(run):
    return timings_mean(run, ["phase1/unify"])
