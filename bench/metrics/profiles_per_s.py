"""Profiles of every complete analysis in the window over the whole
window's seconds (host clock), the time between analyses included:
profiles on disk to a complete database."""


def read(run):
    if not run.done or run.window_s <= 0:
        return None
    return sum(a["summary"]["profiles"] for a in run.done) / run.window_s
