"""Paper Fig. 6 analog: I/O vs compute fraction of the analysis run.

The paper measures 36.3% I/O and <11% compute for their 420-thread run;
our engine records the self seconds of every span of an analysis (summed
over threads), giving the same breakdown for the container-scale workload:
I/O is the ``*/load`` and ``*/write`` spans, compute every other span but
the waits (``*wait``), in which a thread does no work.
"""
from __future__ import annotations

import tempfile

from benchmarks.workloads import generate_timing_workload
from repro.core.aggregate import AggregationConfig, StreamingAggregator


def io_compute_seconds(timings: dict) -> tuple[float, float]:
    """(I/O, compute) thread-seconds from an analysis's span self times."""
    io = comp = 0.0
    for key, sec in timings.items():
        if "/" not in key or key.endswith("wait"):
            continue
        if key.endswith(("/load", "/write")):
            io += sec
        else:
            comp += sec
    return io, comp


def run(out=print):
    with tempfile.TemporaryDirectory() as td:
        paths, _, _ = generate_timing_workload(td + "/in", n_profiles=48)
        res = StreamingAggregator(td + "/out",
                                  AggregationConfig(n_threads=4)).run(paths)
        t = res.timings
        total = t.get("total", 1.0)
        thread_time = 4 * total  # 4 workers: fractions are of thread-time
        io, comp = io_compute_seconds(t)
        out(f"fig6.breakdown,{total*1e6:.0f},"
            f"io_frac={io/thread_time:.3f};compute_frac={comp/thread_time:.3f}"
            f";idle_frac={max(0, 1-(io+comp)/thread_time):.3f}"
            f";cms_frac={t.get('cms', 0)/total:.3f}"
            f";paper_io_frac=0.363;paper_compute_frac=0.11")
    return t


if __name__ == "__main__":
    run()
