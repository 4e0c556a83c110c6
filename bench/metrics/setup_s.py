"""Seconds from process start to the window: JAX and TPU start-up, the
fleet drawn and written, one warm analysis and the column-class warm-up
(host clock)."""


def read(run):
    return run.setup_s
