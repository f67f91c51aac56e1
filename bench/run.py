#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell names a configuration (``bench/configs/<name>.json``), whose
``driver`` (``bench/drivers/<driver>.py``) builds the system, and a
traffic mix (``bench/traffic/<name>.json``). Set-up runs from process
start to the first timed batch: data from the seed, the system, and a
warm-up round that compiles every program the window uses. The window
then runs whole rounds for ``--seconds``. After it, the program's state
is freed and what warm-up and the window produced, kept on the host, is
compared with the plain reference (``bench/refs/<reference>.py``, named
by the configuration, which also counts the training FLOPs a sample);
every number compared is printed beside its limit
(``bench/limits/<cell>.json``).

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1``
profiles a slice of a few seconds inside the window, with the program's
telemetry on, and reports the per-layer metrics, each read by its own
file ``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object. Without an
accelerator, or with fewer chips than the cell asks for, the command
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, Optional, Sequence  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import check  # noqa: E402
import harness  # noqa: E402
import trace_reduce  # noqa: E402

SLICE_START = 0.3      # share of the window before the traced slice
SLICE_SECONDS = 3.0


def bench_marker(x):
    """A program of the harness's own, run once as the trace starts: its
    host and device times tie the harness's spans to the trace clock."""
    return x + 1


MARKER = "jit_bench_marker"


class Slice:
    """Profiles a few seconds inside the window. ``tick(n)`` is called at
    the start of every training step with the samples it trains; the
    trace starts and stops there, so the slice holds whole steps. The
    host tracer keeps level-1 events only; even so it records each chunk
    of the runtime's host-side transposes and slows the host loop it
    watches, so the rates come from the rest of the window. The
    harness's own spans label the idle gaps, brought onto the trace
    clock by the marker's one run."""

    def __init__(self, seconds: float, out_dir: Optional[str]):
        self.start_after = SLICE_START * seconds
        self.length = min(SLICE_SECONDS, 0.5 * seconds)
        self.out_dir = out_dir
        self.t0: Optional[float] = None
        self.started = self.stopped = False
        self.samples = 0
        # t_begin/t_end bound the profiler's start and stop; t_on/t_off
        # the steps that ran while it traced
        self.t_begin = self.t_on = self.t_off = self.t_end = 0.0
        self.marks = (0.0, 0.0)
        if out_dir is not None:          # compiled in set-up
            import jax
            import jax.numpy as jnp
            self.marker = jax.jit(bench_marker)
            self.marker_x = jnp.zeros((8, 128), jnp.float32)
            self.marker(self.marker_x).block_until_ready()

    def arm(self) -> None:
        self.t0 = time.perf_counter()

    def tick(self, n: int) -> None:
        if self.out_dir is None or self.t0 is None or self.stopped:
            return
        now = time.perf_counter()
        if not self.started:
            if now - self.t0 < self.start_after:
                return
            import jax
            self.t_begin = now
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            m0 = time.perf_counter()
            self.marker(self.marker_x).block_until_ready()
            self.marks = (m0, time.perf_counter())
            self.started, self.t_on = True, self.marks[1]
        elif now - self.t_on >= self.length:
            self.finish()
            return
        self.samples += n

    def finish(self) -> None:
        if self.started and not self.stopped:
            import jax
            self.t_off = time.perf_counter()
            jax.profiler.stop_trace()
            self.t_end = time.perf_counter()
        self.stopped = True

    def path(self) -> Optional[str]:
        if not self.started:
            return None
        found = sorted(Path(self.out_dir).rglob("*.xplane.pb"))
        return str(found[-1]) if found else None


def require_chips(chips: int):
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform not in ("tpu", "gpu"):
        raise SystemExit(f"bench/run.py: needs an accelerator, JAX found "
                         f"platform {platform!r}; there is no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"bench/run.py: the cell needs {chips} chips, "
                         f"JAX found {len(devs)} {platform} devices")
    return devs


def device_info(devs) -> Dict[str, Any]:
    d = devs[0]
    try:
        peak = max(int((x.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for x in devs)
    except Exception:
        peak = 0
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _obs_spans(snap) -> Dict[str, list]:
    out: Dict[str, list] = {}
    if not snap:
        return out
    ev = snap["events"]
    for i, (k, dur) in enumerate(zip(ev["name_idx"], ev["dur_ns"])):
        out.setdefault(ev["names"][int(k)], []).append(
            (float(dur) * 1e-9, ev["attrs"].get(str(i), {})))
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, root: Path = harness.ROOT,
             overrides: Optional[Dict[str, Dict[str, Any]]] = None,
             variants: Sequence[str] = (),
             record: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """One run of one cell; returns the result object. ``require_chip``
    and ``overrides`` (``config``/``traffic`` keys) exist for the
    harness's own tests, which run it on the CPU at a small size.
    ``variants`` (``cellbase.VARIANTS``) judges, besides the program,
    the control and planted faults put in its place, and ``record``
    receives every number of the program and of each variant, those the
    limits leave out too (``control.py``)."""
    import jax
    from repro.launch.compile_cache import enable_compilation_cache
    spec = harness.cell(workload, root)
    for part, values in (overrides or {}).items():
        spec[part].update(values)
    devs = (require_chips(spec["workload"]["chips"]) if require_chip
            else jax.devices())
    if require_chip:
        enable_compilation_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = harness.CompileCounter()
    config, traffic = spec["config"], spec["traffic"]

    spans = harness.Spans(trace)
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    prof = Slice(seconds, tmp)
    telemetry = None
    if trace:
        from repro.obs import telemetry
        telemetry.enable()
    cell = None
    try:
        drv = harness.driver(config["driver"], root)
        ref = harness.reference(config["reference"], root)
        cell = drv.Cell(config, traffic, seed, spans, tick=prof.tick,
                        reference=ref)
        cell.setup()
        setup_s = time.perf_counter() - T_START
        spans.reset()
        if telemetry is not None:
            telemetry.snapshot(reset=True)
        compiles0 = counter.total()
        prof.arm()
        stats = cell.window(seconds)
        prof.finish()
        compiles = counter.total() - compiles0
        obs = _obs_spans(telemetry.snapshot(reset=True)) if telemetry \
            else {}
        device = device_info(devs)
        rates = {}
        if prof.started:
            # the traced slice's own rate beside the rest of the window's,
            # which leaves out the profiler's start and stop as well
            rates = {"slice_samples_per_s":
                     prof.samples / (prof.t_off - prof.t_on),
                     "rest_samples_per_s": (stats["samples"] - prof.samples)
                     / (stats["elapsed_s"] - (prof.t_end - prof.t_begin))}
        step_program = cell.step_program
        cell.release()
        gc.collect()
        numbers = cell.numbers()
        verdict = check.judge(numbers, spec["limits"])
        others = cell.variants(variants, numbers)
        judged = {name: check.judge(nums, spec["limits"])
                  for name, nums in others.items()}
        if record is not None:
            record.update(numbers=numbers, variants=others)
        metrics: Dict[str, Dict[str, Any]] = {}
        breakdown = None
        if not trace:
            values = {
                "samples_per_s": stats["samples"] / stats["elapsed_s"],
                "stall_p50_ms": 1e3 * harness.percentile(stats["stalls_s"],
                                                         50),
                "stall_p95_ms": 1e3 * harness.percentile(stats["stalls_s"],
                                                         95),
                "setup_s": setup_s,
            }
            for m in spec["end_to_end"]:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        else:
            path = prof.path()
            reduced = None
            if path:
                raw = trace_reduce.read(path)
                raw["spans"] = trace_reduce.on_trace_clock(
                    raw, MARKER, prof.marks, spans.intervals)
                reduced = trace_reduce.reduce(raw)
            ctx = {
                "trace": reduced, "spans": spans.durations, "obs": obs,
                "compiles_in_window": compiles,
                "step_program": step_program,
                "rest_samples_per_s": rates.get("rest_samples_per_s"),
                "train_flops_per_sample": ref.train_flops_per_sample(
                    config),
                "peak": (harness.peaks(device["kind"], root)
                         if require_chip else None),
            }
            for m in spec["per_layer"]:
                v = harness.metric_reader(m["name"], root)(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            if reduced is not None:
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                breakdown = {"device_ops": trace_reduce.top_ops(
                    reduced["ops"]), "idle_gaps": reduced["gaps"]}
        result: Dict[str, Any] = {
            "correct": verdict["correct"],
            "attempted": stats["batches"] + stats["migrations"],
            "failed": stats["failed"],
            "metrics": metrics, "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["window"] = {
            "seconds": stats["elapsed_s"], "samples": stats["samples"],
            "batches": stats["batches"],
            "migrations": stats["migrations"],
            "migrations_checked": len(cell.migs),
            "stalls": len(stats["stalls_s"]),
            "compiles_in_window": compiles, "setup_s": setup_s, **rates}
        if judged:
            result["variants"] = judged
        result["checks"] = verdict["checks"]
        return result
    finally:
        if cell is not None:
            cell.release()
        if telemetry is not None:
            telemetry.disable()
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for name, row in result["checks"].items():
        ok = row["value"] <= row["limit"]
        print(f"check {name} {row['value']!r} limit {row['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
