"""Reduce a JAX profiler trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device
planes are named ``/device:<KIND>:<n>``; on a TPU, the line ``XLA Ops``
holds one event per operation run and ``XLA Modules`` one per program
run. Host events, where the host tracer recorded them, are on the same
clock; the harness's own spans are on the host's ``perf_counter`` and
are brought onto the trace's clock by ``on_trace_clock`` from one run
of a marker program whose host and device times are both known.

``reduce`` returns plain numbers and names:

``window_s``    first to last device operation of the slice. The device
                tracer keeps a bounded number of events, so a long slice
                holds device events for only part of its host time; the
                window is where both tracers saw everything.
``busy_s``      union of the device-op intervals, averaged over the
                device planes that ran an operation.
``ops``         summed device time per operation name (``op_counts``:
                its runs).
``modules``     per program name (the part before ``(``): runs and summed
                device time.
``gaps``        idle intervals between device ops, longest first, each
                labelled by the innermost host span around its middle
                (``other`` where none is).
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float, str]     # start s, end s, name

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _events(line) -> Iterable[Interval]:
    for e in line.events:
        s = e.start_ns * 1e-9
        yield s, s + e.duration_ns * 1e-9, e.name


def read(path: str) -> Dict[str, list]:
    """Raw intervals per device plane from one file; ``spans`` is left
    for the caller to fill (``on_trace_clock``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[str, Dict[str, List[Interval]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops: List[Interval] = []
            modules: List[Interval] = []
            for ln in plane.lines:
                if ln.name == OPS_LINE:
                    ops.extend(_events(ln))
                elif ln.name == MODULES_LINE:
                    modules.extend(_events(ln))
            if ops:
                devices[plane.name] = {"ops": ops, "modules": modules}
    return {"devices": devices, "spans": []}


def on_trace_clock(raw: Dict[str, list], marker: str,
                   host_marks: Tuple[float, float],
                   intervals: Iterable[Interval]) -> List[Interval]:
    """``intervals`` on the host clock, shifted onto the trace's clock.
    The marker program's first run lies on the device between the host
    times ``host_marks`` (its call and its return); the shift is the
    mean of the two bounds that gives. Nothing when the trace holds no
    run of the marker."""
    runs = sorted((s, e) for d in raw["devices"].values()
                  for s, e, name in d["modules"]
                  if name.split("(")[0] == marker)
    if not runs:
        return []
    (s, e), (h0, h1) = runs[0], host_marks
    shift = ((s - h0) + (e - h1)) / 2
    return [(a + shift, b + shift, name) for a, b, name in intervals]


def union_length(intervals: Iterable[Interval], lo: float, hi: float
                 ) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of intervals clipped to [lo, hi], and the gaps
    between them inside [lo, hi]."""
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None:
            if s > lo:
                gaps.append((lo, s))
            cur_s, cur_e = s, e
        elif s > cur_e:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is None:
        return 0.0, [(lo, hi)] if hi > lo else []
    busy += cur_e - cur_s
    if hi > cur_e:
        gaps.append((cur_e, hi))
    return busy, gaps


def _label(t: float, spans: Sequence[Interval]) -> str:
    best, width = "other", float("inf")
    for s, e, name in spans:
        if s <= t <= e and e - s < width:
            best, width = name, e - s
    return best


def reduce(raw: Dict[str, list], top: int = 10) -> Dict[str, object]:
    devices, spans = raw["devices"], raw["spans"]
    every = [iv for d in devices.values() for iv in d["ops"]]
    if not every:
        return {"window_s": 0.0, "busy_s": 0.0, "ops": {}, "op_counts": {},
                "modules": {}, "gaps": [], "devices": 0}
    lo = min(s for s, _, _ in every)
    hi = max(e for _, e, _ in every)
    ops: Dict[str, float] = {}
    op_counts: Dict[str, int] = {}
    modules: Dict[str, List[float]] = {}
    busy_total, gaps = 0.0, []
    for d in devices.values():
        busy, g = union_length(d["ops"], lo, hi)
        busy_total += busy
        gaps.extend(g)
        for s, e, name in d["ops"]:
            ops[name] = ops.get(name, 0.0) + (e - s)
            op_counts[name] = op_counts.get(name, 0) + 1
        for s, e, name in d["modules"]:
            m = modules.setdefault(name.split("(")[0], [0, 0.0])
            m[0] += 1
            m[1] += e - s
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [[_label((a + b) / 2, spans), b - a] for a, b in gaps[:top]]
    return {"window_s": hi - lo, "busy_s": busy_total / len(devices),
            "ops": ops, "op_counts": op_counts, "modules": modules,
            "gaps": labelled,
            "devices": len(devices)}


def short(op: str, width: int = 120) -> str:
    """An operation's HLO text without layouts, cut to ``width``."""
    return re.sub(r"\{[^{}]*\}", "", op)[:width]


def top_ops(ops: Dict[str, float], top: int = 10) -> List[list]:
    return [[short(n), t] for n, t in
            sorted(ops.items(), key=lambda kv: -kv[1])[:top]]
