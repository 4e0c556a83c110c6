"""Thread-seconds of the CMS gather, the context-major transpose of each
context group on the CMS workers: ``analyze``'s ``timings["cms/gather"]``
(a program span's self time, summed over threads), mean over the run's
complete analyses."""
from bench.spans import timings_mean


def read(run):
    return timings_mean(run, ["cms/gather"])
