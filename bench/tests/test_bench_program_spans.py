"""The per-layer metrics that read the program's own telemetry spans,
each against a hand-built context. Values computed by hand."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402

NEW = ("input.put_ms", "split_step.dispatch_ms", "split_step.readback_ms",
       "scheduler.cost_ms", "migration.compile_ms", "migration.fetch_ms",
       "migration.restore_ms")


def _obs():
    # two steps, two moves that compiled twice; durations in seconds
    return {"sched.put": [(0.002, {}), (0.004, {})],
            "sched.dispatch": [(0.0005, {}), (0.0007, {})],
            "sched.readback": [(0.001, {}), (0.003, {})],
            "sched.cost": [(0.00002, {}), (0.00004, {})],
            "sched.move": [(0.07, {"client": "pi3_1"}),
                           (0.06, {"client": "pi4_1"})],
            "mig.fetch": [(0.008, {"bytes": 2_000_000}),
                          (0.010, {"bytes": 2_000_000})],
            "sched.restore": [(0.003, {}), (0.004, {})],
            "jit.compile": [(0.010, {"fun": "_quantize"}),
                            (0.005, {"fun": "_dequantize"})]}


@pytest.mark.parametrize("name, value", [
    ("input.put_ms", 3.0),              # (2 + 4) ms over 2 steps
    ("split_step.dispatch_ms", 0.6),
    ("split_step.readback_ms", 2.0),
    ("scheduler.cost_ms", 0.03),
    ("migration.compile_ms", 7.5),      # (10 + 5) ms over 2 moves
    ("migration.fetch_ms", 9.0),
    ("migration.restore_ms", 3.5),
])
def test_program_span_metrics(name, value):
    assert harness.metric_reader(name)({"obs": _obs()}) \
        == pytest.approx(value)


def test_no_compile_in_a_window_with_moves_reads_zero():
    obs = dict(_obs(), **{"jit.compile": []})
    assert harness.metric_reader("migration.compile_ms")({"obs": obs}) == 0


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_reports_nothing(name):
    # the parent program records only mig.* spans
    ctx = {"obs": {"mig.pack": [(0.03, {})], "mig.unpack": [(0.02, {})]}}
    assert harness.metric_reader(name)(ctx) is None


def test_each_new_metric_is_in_the_spec_for_its_cells():
    spec = {m["name"]: m for m in harness.spec()["per_layer"]}
    for name in NEW:
        assert spec[name]["source"] == "program_span"
    for name in ("migration.compile_ms", "migration.fetch_ms",
                 "migration.restore_ms"):
        assert spec[name]["workloads"] == ["testbed-handoff"]
        assert spec[name]["moves"] == "stall_p95_ms"
