"""Seconds of phase 2, remapping, combining, propagating and writing every
profile's plane up to the host copy of each device result: ``analyze``'s
``timings.phase2`` (a program span), mean over the run's complete
analyses."""


def read(run):
    return run.mean_timing("phase2")
