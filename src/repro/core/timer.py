"""Where an analysis spends its time: the timer behind
``AnalysisResult.timings``.

One dict holds three kinds of key:

* **phases** (``phase1``, ``phase2``, ``completion``, ``cms``): wall
  seconds on the thread that runs the analysis, opened with
  ``span(name, wall=True)``; ``total`` is added directly;
* **spans** (``phase2/densify``, ``device/h2d``, ...; ``/`` separates the
  levels of the tree in docs/architecture.md): self seconds, a span's
  duration less the spans opened inside it on the same thread, summed
  over threads, so a worker-thread span reads as thread-seconds per
  analysis;
* **counters** (``device_h2d_bytes``, ``device_*_launches``,
  ``sink_peak``, ...), added with :meth:`PhaseTimer.add`.

Every span and phase also opens a ``jax.profiler.TraceAnnotation`` of its
name, which puts it on the device trace's clock in a profiler session and
costs next to nothing outside one.  The timer never imports JAX for this:
it annotates only once ``jax`` is loaded, so the CPU path stays free of it.
"""
from __future__ import annotations

import contextlib
import sys
import threading
import time


def _annotation(name: str):
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(name)


class PhaseTimer:
    """Phase wall times, span self times and counters of one analysis,
    shared by every thread of it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self.acc: dict[str, float] = {}

    def add(self, key: str, value: float) -> None:
        with self._lock:
            self.acc[key] = self.acc.get(key, 0.0) + value

    @contextlib.contextmanager
    def span(self, name: str, *, wall: bool = False):
        """Time the block under ``name``: its self time, or with ``wall``
        its whole duration.  Either way the enclosing span on this thread
        does not count the block as its own."""
        stack = self._local.__dict__.setdefault("stack", [])
        stack.append(0.0)   # seconds of the spans opened inside this one
        t0 = time.perf_counter()
        try:
            with _annotation(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            inner = stack.pop()
            if stack:
                stack[-1] += dt
            self.add(name, dt if wall else dt - inner)
