"""Seconds of the CMS census, the per-context value and metric counts over
every PMS plane, on the host and on the device: ``analyze``'s
``timings["cms/census"] + timings["cms/census_device"]`` (program spans'
self times), mean over the run's complete analyses."""
from bench.spans import timings_mean


def read(run):
    return timings_mean(run, ["cms/census", "cms/census_device"])
