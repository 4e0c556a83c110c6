"""Thread-seconds of phase 2's densify, the (contexts x metrics) float32
matrix built for each profile's device propagation: ``analyze``'s
``timings["phase2/densify"]`` (a program span's self time, summed over
worker threads), mean over the run's complete analyses."""
from bench.spans import timings_mean


def read(run):
    return timings_mean(run, ["phase2/densify"])
