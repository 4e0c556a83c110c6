"""Seconds of phase 1, parsing every profile and unifying the context
trees: ``analyze``'s ``timings.phase1`` (a program span), mean over the
run's complete analyses."""


def read(run):
    return run.mean_timing("phase1")
