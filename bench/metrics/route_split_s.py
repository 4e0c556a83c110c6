"""Thread-seconds of phase 2's route split, where each placeholder's
entries are combined and split over its route leaves by normalised weight
(paper §4.1.3): ``analyze``'s ``timings["phase2/routes"]`` (a program
span's self time, summed over worker threads), mean over the run's
complete analyses.  0 on a fleet without a routed op, where the program
counts no placeholder and so never opens the span; None from a program
without the span or the counter."""
from bench.spans import timings_mean


def read(run):
    split = timings_mean(run, ["phase2/routes"])
    if split is None and timings_mean(run, ["route_placeholders"]) == 0:
        return 0.0
    return split
