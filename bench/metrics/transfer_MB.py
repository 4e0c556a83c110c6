"""MB (10^6 bytes) copied between host and device in one analysis, both
ways, by the propagation, the combine, the CMS census and the offset scan:
``analyze``'s ``timings["device_h2d_bytes"] +
timings["device_d2h_bytes"]`` (program counters), mean over the run's
complete analyses."""
from bench.spans import timings_mean


def read(run):
    nbytes = timings_mean(run, ["device_h2d_bytes", "device_d2h_bytes"])
    return None if nbytes is None else nbytes / 1e6
