"""Thread-seconds of the propagation's copies between host and device, the
padded matrix to the device and the result back: ``analyze``'s
``timings["device/h2d"] + timings["device/d2h"]`` (program spans' self
times, summed over threads), mean over the run's complete analyses."""
from bench.spans import timings_mean


def read(run):
    return timings_mean(run, ["device/h2d", "device/d2h"])
