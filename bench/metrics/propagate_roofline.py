"""Inclusive propagation's share of its roofline, in %, in the traced
analysis: the least time the work needs on the chip over the device time
of the XLA modules that do it.

The work reads every exclusive value of the database and writes every
inclusive one, as float32 (4 bytes), whatever implements it; the counts
are the reference's, so they move only with the fleet.  Bound by memory:
the least time is those bytes over the chip's HBM bandwidth
(``bench/peaks.json``).  The modules are the ones that implement the
propagation today."""
from bench.trace_reduce import module_seconds

MODULES = ("jit_inclusive_from_exclusive",)
BYTES_PER_VALUE = 4


def read(run):
    if run.trace is None or run.peaks is None or run.reference is None:
        return None
    seconds = module_seconds(run.trace, MODULES)
    if seconds <= 0:
        return None
    ref = run.reference
    nbytes = BYTES_PER_VALUE * (ref.n_exclusive + ref.n_inclusive)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / seconds
