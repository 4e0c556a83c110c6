"""Seconds of the CMS, its census, offset scan and context-major gather:
``analyze``'s ``timings.cms`` (a program span), mean over the run's
complete analyses."""


def read(run):
    return run.mean_timing("cms")
