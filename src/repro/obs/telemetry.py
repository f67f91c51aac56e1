"""Process-local telemetry: spans, counters, gauges, histograms.

Dependency-free (stdlib + numpy, which the repo already requires
everywhere) and **off by default**: every recording entry point checks
one module-level flag first, so an instrumented hot path costs a single
attribute load + branch when telemetry is disabled. The instrumentation
observes *wall* clocks only (``time.monotonic_ns`` for spans,
``time.time_ns`` for cross-process alignment) — never simulated time,
never numerics — which is what keeps telemetry orthogonal to the
simulator's bit-identity contract.

Collection model:

* **Spans** (``with span("name", k=v): ...``) append one fixed-shape
  tuple to a per-thread ring buffer (``collections.deque(maxlen=N)``
  — appends are GIL-atomic, so no lock is taken on the hot path; a
  full ring drops the *oldest* events and counts the drops).
* **Counters / gauges / histograms** live in one process-local
  registry behind a small lock; they are updated at frame/window
  granularity, never per simulated event.
* ``record(name, t0_ns, dur_ns, **attrs)`` appends a span that has
  already finished, timed elsewhere; ``from_wall`` puts a
  ``time.time()`` reading on the span clock.
* **Compiles**: ``enable()`` registers, once per process and only where
  JAX is already imported (the plane never imports it), a
  ``jax.monitoring`` listener that records every XLA backend compile,
  or the persistent-cache load standing in for it, as a ``jit.compile``
  span (attr ``fun``).
* ``snapshot(reset=True)`` drains everything into a plain, wire-
  encodable tree (string-keyed dicts, numpy columns, scalar leaves) —
  the exact payload the ``stats`` record-plane message carries (see
  docs/ARCHITECTURE.md) and the unit ``repro.obs.trace`` merges into a
  Chrome trace. Each snapshot carries a paired ``(mono_ns, wall_ns)``
  clock reading so per-process monotonic timestamps can be aligned
  onto one shared unix-time axis.
"""
from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

# span events buffered per thread between drains: a 51 s benchmark
# window of the testbed (about 13k split steps, four spans each, plus
# the moves and compiles) is drained once, at its end, with room for
# four times that rate
RING_CAP = 1 << 18
HIST_SAMPLE_CAP = 4096    # raw values kept per histogram (for percentiles)

COORDINATOR_RANK = -1     # the convention every merge/trace consumer uses


class _Ring:
    __slots__ = ("events", "dropped", "tid", "thread_name")

    def __init__(self, tid: int, thread_name: str):
        self.events: deque = deque(maxlen=RING_CAP)
        self.dropped = 0
        self.tid = tid
        self.thread_name = thread_name


class _Hist:
    __slots__ = ("count", "sum", "min", "max", "sample")

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self.sample: List[float] = []

    def observe(self, v: float) -> None:
        self.count += 1
        self.sum += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v
        if len(self.sample) < HIST_SAMPLE_CAP:
            self.sample.append(v)


class _State:
    def __init__(self):
        self.enabled = False
        self.gen = 0              # bumped by enable(): invalidates old rings
        self.rank: int = COORDINATOR_RANK
        self.process_name = ""
        self.lock = threading.Lock()
        self.local = threading.local()
        self.rings: List[_Ring] = []
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.hists: Dict[str, _Hist] = {}


_state = _State()


def enable(rank: int = COORDINATOR_RANK,
           process_name: Optional[str] = None) -> None:
    """Turn collection on for this process (fresh: prior buffers are
    discarded). ``rank`` tags every snapshot — shard groups use their
    group/host rank, the coordinator uses ``COORDINATOR_RANK``."""
    with _state.lock:
        _state.gen += 1
        _state.rings = []
        _state.counters = {}
        _state.gauges = {}
        _state.hists = {}
    _state.rank = rank
    _state.process_name = process_name or (
        "coordinator" if rank == COORDINATOR_RANK else f"rank {rank}")
    _state.enabled = True
    _watch_compiles()


def disable() -> None:
    _state.enabled = False


def is_enabled() -> bool:
    return _state.enabled


# -- spans -------------------------------------------------------------------

class _Span:
    __slots__ = ("name", "attrs", "t0")

    def __init__(self, name: str, attrs: Optional[Dict[str, Any]]):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        _append(_ring(), (self.name, self.t0, time.monotonic_ns() - self.t0,
                          self.attrs))
        return False


class _NoopSpan:
    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _NoopSpan()


def span(name: str, **attrs):
    """Context manager timing one named region on this thread. Attrs
    must be scalars (anything else is stringified at snapshot time).
    Returns a shared no-op object when telemetry is disabled."""
    if not _state.enabled:
        return _NOOP
    return _Span(name, attrs or None)


def record(name: str, t0_ns: int, dur_ns: int, **attrs) -> None:
    """Append a span that has already ended: ``t0_ns`` on the span clock
    (``time.monotonic_ns``; ``from_wall`` converts), ``dur_ns`` long."""
    if not _state.enabled:
        return
    _append(_ring(), (name, int(t0_ns), int(dur_ns), attrs or None))


def from_wall(wall_s: float) -> int:
    """A ``time.time()`` reading, in seconds, on the span clock."""
    return time.monotonic_ns() - (time.time_ns() - int(wall_s * 1e9))


def _append(ring: "_Ring", event: tuple) -> None:
    if len(ring.events) >= RING_CAP:
        ring.dropped += 1         # deque evicts the oldest on append
    ring.events.append(event)


# JAX reports each backend compile (a persistent-cache load included)
# as a time span whose bounds are time.time() readings
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles_watched = False


def _watch_compiles() -> None:
    global _compiles_watched
    if "jax" not in sys.modules:          # the plane never imports JAX
        return
    with _state.lock:
        if not _compiles_watched:
            import jax.monitoring
            jax.monitoring.register_event_time_span_listener(_on_time_span)
            _compiles_watched = True


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    if event == COMPILE_EVENT:
        record("jit.compile", from_wall(start), int((end - start) * 1e9),
               fun=str(kw.get("fun_name", "")))


def _ring() -> _Ring:
    loc = _state.local
    if getattr(loc, "gen", None) != _state.gen:
        r = _Ring(threading.get_ident(), threading.current_thread().name)
        with _state.lock:
            _state.rings.append(r)
        loc.ring = r
        loc.gen = _state.gen
    return loc.ring


# -- registry metrics --------------------------------------------------------

def count(name: str, n: float = 1) -> None:
    """Monotonic counter increment (e.g. frames/bytes on the wire)."""
    if not _state.enabled:
        return
    with _state.lock:
        _state.counters[name] = _state.counters.get(name, 0) + n


def gauge(name: str, value: float) -> None:
    """Last-value-wins gauge (e.g. chunk-queue depth)."""
    if not _state.enabled:
        return
    with _state.lock:
        _state.gauges[name] = float(value)


def observe(name: str, value: float) -> None:
    """Histogram sample (e.g. barrier wait seconds per window)."""
    if not _state.enabled:
        return
    with _state.lock:
        h = _state.hists.get(name)
        if h is None:
            h = _state.hists[name] = _Hist()
        h.observe(float(value))


# -- snapshot ---------------------------------------------------------------

def _safe_attrs(attrs: Dict[str, Any]) -> Dict[str, Any]:
    return {str(k): (v if isinstance(v, (bool, int, float, str)) else str(v))
            for k, v in attrs.items()}


def snapshot(reset: bool = True) -> Optional[Dict[str, Any]]:
    """Drain everything recorded since the last snapshot into one
    wire-encodable tree (the ``stats`` message payload — normative
    schema in docs/ARCHITECTURE.md §3.6), or None when nothing was
    recorded. Safe to call while other threads keep recording: ring
    drains use atomic ``popleft``, so concurrent appends land in the
    next snapshot instead of being lost."""
    if not _state.enabled:
        return None
    mono_ns = time.monotonic_ns()
    wall_ns = time.time_ns()
    names: List[str] = []
    name_idx: Dict[str, int] = {}
    idx_col: List[int] = []
    tid_col: List[int] = []
    t0_col: List[int] = []
    dur_col: List[int] = []
    attrs_by_event: Dict[str, Dict[str, Any]] = {}
    threads: Dict[str, str] = {}
    dropped = 0
    with _state.lock:
        rings = list(_state.rings)
        counters = dict(_state.counters)
        gauges = dict(_state.gauges)
        hists = {k: {"count": h.count, "sum": h.sum, "min": h.min,
                     "max": h.max, "sample": list(h.sample)}
                 for k, h in _state.hists.items()}
        if reset:
            _state.counters = {}
            _state.hists = {}
    for ring in rings:
        threads[str(ring.tid)] = ring.thread_name
        dropped += ring.dropped
        if reset:
            ring.dropped = 0
        while True:
            try:
                name, t0, dur, attrs = ring.events.popleft()
            except IndexError:
                break
            i = name_idx.get(name)
            if i is None:
                i = name_idx[name] = len(names)
                names.append(name)
            if attrs:
                attrs_by_event[str(len(idx_col))] = _safe_attrs(attrs)
            idx_col.append(i)
            tid_col.append(ring.tid)
            t0_col.append(t0)
            dur_col.append(dur)
    if not (idx_col or counters or gauges or hists):
        return None
    return {
        "rank": _state.rank,
        "pid": os.getpid(),
        "process_name": _state.process_name,
        "clock": {"mono_ns": mono_ns, "wall_ns": wall_ns},
        "threads": threads,
        "events": {
            "names": names,
            "name_idx": np.asarray(idx_col, np.int32),
            "tid": np.asarray(tid_col, np.int64),
            "t0_ns": np.asarray(t0_col, np.int64),
            "dur_ns": np.asarray(dur_col, np.int64),
            "attrs": attrs_by_event,
        },
        "counters": counters,
        "gauges": gauges,
        "hists": hists,
        "dropped": dropped,
    }
