"""The share of the traced analysis, in %, in which the device ran no
operation: 1 - (union of device-op intervals) / (the analysis's span),
from the profiler trace."""


def read(run):
    if run.trace is None or run.trace["idle_share"] is None:
        return None
    return 100.0 * run.trace["idle_share"]
