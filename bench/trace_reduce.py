"""Reduce a ``jax.profiler`` trace to the device metrics of one analysis.

The trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes,
read with ``jax.profiler.ProfileData``.  The analysis is the host span the
benchmark opened around it (``jax.profiler.TraceAnnotation``), and only
device work inside that span counts:

* device planes are ``/device:TPU:<n>``; a trace of the CPU backend has
  none, and reduces to nothing;
* busy time is the union of the intervals of the ``XLA Ops`` line (the
  ``XLA Modules`` line where a plane has no op line), averaged over planes;
* time per XLA module is the summed duration of each module's events on
  the ``XLA Modules`` line, by the module's name without its ``(id)``
  suffix; an op is named by its HLO instruction and the module it ran in;
* idle gaps are the stretches of the span in which the first device ran
  nothing, each named by the host phase it started in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

SPAN = "bench.analysis"
_DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _module_name(name: str) -> str:
    return _SUFFIX.sub("", name.strip())


def _op_name(name: str) -> str:
    """``%segstats.1 = f32[...] custom-call(...)`` -> ``segstats.1``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _device_lines(planes) -> list[dict]:
    """Per device: op intervals [(start_ns, end_ns, op, module)], each op
    named by the module it ran inside, and module intervals [(start_ns,
    end_ns, module)]."""
    devices = []
    for plane in planes:
        if not _DEVICE.match(plane.name):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       _module_name(e.name))
                      for e in lines.get("XLA Modules", []))
        starts = [a for a, _, _ in mods]
        ops = []
        for e in lines.get("XLA Ops", []):
            k = bisect.bisect_right(starts, e.start_ns) - 1
            mod = mods[k][2] if k >= 0 and e.start_ns < mods[k][1] else ""
            ops.append((e.start_ns, e.start_ns + e.duration_ns,
                        _op_name(e.name), mod))
        if not ops:
            ops = [(a, b, m, m) for a, b, m in mods]
        devices.append({"ops": ops, "modules": mods})
    return devices


def _span(planes, name: str) -> tuple[float, float] | None:
    for plane in planes:
        if _DEVICE.match(plane.name):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name == name:
                    return e.start_ns, e.start_ns + e.duration_ns
    return None


def reduce_planes(planes, phases=(), span_name: str = SPAN) -> dict | None:
    """Device metrics of the span; ``phases`` are ``(label, start_s,
    end_s)`` offsets from the span's start that name the idle gaps.
    Returns None when the trace holds no span or no device operation."""
    span = _span(planes, span_name)
    devices = _device_lines(planes)
    if span is None or not devices:
        return None
    s0, s1 = span

    def clip(iv):
        return [(max(a, s0), min(b, s1)) + tuple(rest)
                for a, b, *rest in iv if b > s0 and a < s1]

    busy, module_ns, op_ns = [], {}, {}
    for dev in devices:
        ops = clip(dev["ops"])
        busy.append(sum(b - a for a, b in _union([(a, b) for a, b, *_ in ops])))
        for a, b, mod in clip(dev["modules"]) or [(a, b, m) for a, b, _, m in ops]:
            module_ns[mod] = module_ns.get(mod, 0.0) + (b - a)
        for a, b, op, mod in ops:
            key = f"{mod}/{op}" if mod else op
            op_ns[key] = op_ns.get(key, 0.0) + (b - a)
    window_ns = s1 - s0
    busy_ns = sum(busy) / len(busy)

    gaps, t = [], s0
    for a, b in _union([(a, b) for a, b, *_ in clip(devices[0]["ops"])]) + [(s1, s1)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)

    def phase_of(ns: float) -> str:
        off = (ns - s0) / 1e9
        for label, lo, hi in phases:
            if lo <= off < hi:
                return label
        return "other"

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns if window_ns > 0 else None,
        "module_s": {k: v / 1e9 for k, v in module_ns.items()},
        "top_ops": [[k, v / 1e9] for k, v in
                    sorted(op_ns.items(), key=lambda kv: kv[1], reverse=True)[:10]],
        "idle_gaps": [[f"{phase_of(a)}+{(a - s0) / 1e9:.3f}s", (b - a) / 1e9]
                      for a, b in gaps[:10]],
    }


def reduce_file(path: str, phases=(), span_name: str = SPAN) -> dict | None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)   # the planes are views into it
    return reduce_planes(list(data.planes), phases, span_name)


def module_seconds(reduced: dict | None, modules) -> float:
    """Summed device seconds of the named XLA modules (0 where none ran)."""
    if not reduced:
        return 0.0
    return sum(v for k, v in reduced["module_s"].items() if k in modules)
