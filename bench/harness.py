"""Pieces of the benchmark that every cell shares.

Nothing here touches JAX at import time, and nothing describes a device
topology: the entry point decides when JAX starts.

- ``spec``/``cell``: ``BENCHMARK.json`` and the files it names, found by
  name (``configs/<config>.json``, ``traffic/<mix>.json``,
  ``limits/<workload>.json``, ``metrics/<metric>.py``,
  ``drivers/<driver>.py``, ``refs/<reference>.py``).
  What depends on the model lives in the configuration's own files, so a
  new configuration adds files and edits none: its file names a
  ``driver``, whose ``Cell`` builds and runs the system and gives
  ``merge`` and ``first_grad`` (``cellbase``; the testbed driver's hooks
  can be overridden by a subclass in a file of its own), and a
  ``reference``, whose ``init(seed, config)``, ``train_steps(params,
  batches, config, dtype, precision)`` and
  ``train_flops_per_sample(config)`` give the plain reference and the
  operations a sample of training needs (``refs/vgg5.py``).
- ``Spans``: the harness's own host spans around calls into the program.
- ``CompileCounter``: backend compiles, counted by a ``jax.monitoring``
  listener.
"""
from __future__ import annotations

import importlib.util
import json
import math
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Any, Callable, Dict, List, Tuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# -- files, found by name ---------------------------------------------------

def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def spec(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def _by_name(entries: List[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path, name: str):
    """Import one file by path (names may hold '.' and '-')."""
    mod_spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    if mod_spec is None or mod_spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def cell(workload: str, root: Path = ROOT) -> Dict[str, Any]:
    """Everything one cell needs: its entry, its configuration and
    traffic files, its limits and the metrics it reports."""
    s = spec(root)
    wl = _by_name(s["workloads"], workload, "workload")
    cfg_entry = _by_name(s["configs"], wl["config"], "config")
    config = load_json(root / cfg_entry["file"])
    bench = root / "bench"
    traffic = load_json(bench / "traffic" / f"{wl['traffic']}.json")
    limits = load_json(bench / "limits" / f"{workload}.json")

    def applies(m):
        return workload in m.get("workloads", [wl["name"]])

    return {
        "workload": wl, "config": config, "traffic": traffic,
        "limits": limits,
        "end_to_end": [m for m in s["end_to_end"] if applies(m)],
        "per_layer": [m for m in s["per_layer"] if applies(m)],
    }


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       name).read


def driver(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "drivers" / f"{name}.py", name)


def reference(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "refs" / f"{name}.py", name)


def peaks(device_kind: str, root: Path = ROOT) -> Dict[str, Any]:
    table = load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       "bench/peaks.json; add its published peaks")
    return table["devices"][device_kind]


# -- host spans -------------------------------------------------------------

class Spans:
    """Host-clock spans the harness records around calls into the
    program, in traced runs only (``on``): their durations by name, and
    their (start, end, name) intervals, from which the trace reduction
    says what the host was doing in an idle gap of the device."""

    def __init__(self, on: bool = False):
        self.on = on
        self.durations: Dict[str, List[float]] = {}
        self.intervals: List[Tuple[float, float, str]] = []

    @contextmanager
    def span(self, name: str):
        if not self.on:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            self.durations.setdefault(name, []).append(t1 - t0)
            self.intervals.append((t0, t1, name))

    def wrap(self, name: str, fn: Callable) -> Callable:
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped

    def reset(self) -> None:
        self.durations = {}
        self.intervals = []


class CompileCounter:
    """Counts XLA backend compiles in this process (a ``jax.monitoring``
    listener; a persistent-cache hit skips the backend compile and is
    counted apart)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **kw):
        if name == self.COMPILE:
            self.compiles += 1

    def _on_event(self, name, **kw):
        if name == self.CACHE_HIT:
            self.cache_hits += 1

    def total(self) -> int:
        return self.compiles + self.cache_hits


# -- small statistics ---------------------------------------------------------

def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile over all values (no interpolation)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    k = max(0, min(len(xs) - 1, math.ceil(q / 100.0 * len(xs)) - 1))
    return xs[k]
