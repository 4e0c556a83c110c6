"""Thread-seconds of loading the structure files each profile names:
``analyze``'s ``timings["phase1/structures"]`` (a program span's self
time, summed over threads), mean over the run's complete analyses."""
from bench.spans import timings_mean


def read(run):
    return timings_mean(run, ["phase1/structures"])
