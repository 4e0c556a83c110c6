"""Thread-seconds that phase-2 workers spend parked in the device batching
funnel while another thread's propagation launch runs: ``analyze``'s
``timings["device/wait"]`` (a program span's self time, summed over
threads), mean over the run's complete analyses."""
from bench.spans import timings_mean


def read(run):
    return timings_mean(run, ["device/wait"])
