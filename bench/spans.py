"""The program's own spans, as the benchmark reads them.

``repro.launch.analyze`` times itself with spans (``phase2/densify``,
``device/h2d``, ``cms/gather``, ...; ``/`` separates the levels) and
counters.  They reach the benchmark two ways:

* their self seconds and the counters, summed over threads, in the
  summary's ``timings``: :func:`timings_mean`;
* their intervals, in a ``jax.profiler`` trace: each span opens a
  ``TraceAnnotation`` of its name, so it lies on a thread line of the
  ``/host:CPU`` plane, on the device trace's clock.  :func:`idle_attribution`
  sets them against the device's idle intervals inside the benchmark's own
  span (``bench.analysis``), which ``bench/trace_reduce.py`` finds.

A leaf span is a ``<level>/<step>`` span; the phases around them
(``phase1``, ``phase2``, ``completion``, ``cms``) are not leaves.  Nothing
of the program is imported: only the names in the trace are read.
"""
from __future__ import annotations

import re

from bench.trace_reduce import SPAN, _device_lines, _span, _union

LEAF = re.compile(r"^(phase1|phase2|device|completion|cms)/\w+$")
HOST = "/host:CPU"


def timings_mean(run, keys) -> float | None:
    """The sum of ``keys`` in ``timings``, mean over the run's complete
    analyses that report any of them."""
    sums = [sum(t[k] for k in keys if k in t)
            for t in (a["summary"]["timings"] for a in run.done)
            if any(k in t for k in keys)]
    return sum(sums) / len(sums) if sums else None


def leaf_spans(planes, s0: float, s1: float) -> list[list[tuple]]:
    """Per host thread line, its leaf spans ``(start_ns, end_ns, name)``
    clipped to ``[s0, s1)``."""
    lines = []
    for plane in planes:
        if plane.name != HOST:
            continue
        for ln in plane.lines:
            found = [(max(e.start_ns, s0), min(e.start_ns + e.duration_ns, s1),
                      e.name)
                     for e in ln.events if e.duration_ns > 0
                     and LEAF.match(e.name)
                     and e.start_ns < s1 and e.start_ns + e.duration_ns > s0]
            if found:
                lines.append(found)
    return lines


def idle_intervals(planes, s0: float, s1: float) -> list[tuple[float, float]]:
    """Where the first device ran no operation inside ``[s0, s1)``."""
    devices = _device_lines(planes)
    busy = _union([(max(a, s0), min(b, s1)) for a, b, *_ in devices[0]["ops"]
                   if b > s0 and a < s1])
    gaps, t = [], s0
    for a, b in busy + [(s1, s1)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    return gaps


def idle_attribution(planes, span_name: str = SPAN) -> dict | None:
    """The device-idle seconds of the span during which some leaf span is
    open on a host thread (``share`` of all idle seconds), and the idle
    seconds by innermost open span (``by_span``): a stretch in which ``k``
    threads have a leaf span open counts ``1/k`` to each thread's innermost
    one.  None without the span, a device plane or any leaf span."""
    span = _span(planes, span_name)
    if span is None or not _device_lines(planes):
        return None
    s0, s1 = span
    lines = leaf_spans(planes, s0, s1)
    if not lines:
        return None
    idle = idle_intervals(planes, s0, s1)
    # sweep: at equal times idle edges come first, then span ends, then
    # span starts, the longer span first (it is the outer one)
    events = [(a, 0, 0, 1, None) for a, _ in idle] + \
             [(b, 0, 0, -1, None) for _, b in idle]
    for k, spans in enumerate(lines):
        for a, b, name in spans:
            events += [(a, 2, -b, k, name), (b, 1, 0, k, name)]
    events.sort(key=lambda e: e[:3])
    open_: list[list[str]] = [[] for _ in lines]
    in_idle, t_prev = 0, s0
    by_span: dict[str, float] = {}
    idle_ns = attributed_ns = 0.0
    for t, kind, _, k, name in events:
        if in_idle and t > t_prev:
            dt = t - t_prev
            idle_ns += dt
            inner = [stack[-1] for stack in open_ if stack]
            if inner:
                attributed_ns += dt
                for n in inner:
                    by_span[n] = by_span.get(n, 0.0) + dt / len(inner)
        t_prev = t
        if kind == 0:
            in_idle += k
        elif kind == 2:
            open_[k].append(name)
        else:
            open_[k].remove(name)
    if idle_ns <= 0:
        return None
    return {"idle_s": idle_ns / 1e9, "attributed_s": attributed_ns / 1e9,
            "share": attributed_ns / idle_ns,
            "by_span": [[n, v / 1e9] for n, v in
                        sorted(by_span.items(), key=lambda kv: -kv[1])]}


def idle_attribution_file(path: str, span_name: str = SPAN) -> dict | None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)   # the planes are views into it
    return idle_attribution(list(data.planes), span_name)
