"""``split_step.syncs_per_step``: the program's loss read-backs per
training step, against hand-built contexts."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

NAME = "split_step.syncs_per_step"


def _spans(n):
    return [(0.001, {})] * n


@pytest.mark.parametrize("reads, steps, value", [
    (2, 8, 0.25),       # two sync points in eight steps
    (8, 8, 1.0),        # a read after every step
])
def test_reads_over_steps(reads, steps, value):
    ctx = {"obs": {"sched.readback": _spans(reads),
                   "sched.dispatch": _spans(steps)}}
    assert harness.metric_reader(NAME)(ctx) == pytest.approx(value)


@pytest.mark.parametrize("obs", [
    {},
    {"sched.dispatch": _spans(8)},
    {"sched.readback": _spans(2)},
    {"mig.pack": _spans(1), "mig.unpack": _spans(1)},
])
def test_without_the_spans_it_reports_nothing(obs):
    assert harness.metric_reader(NAME)({"obs": obs}) is None


def test_in_the_spec_for_both_cells():
    [m] = [m for m in harness.spec()["per_layer"] if m["name"] == NAME]
    assert (m["source"], m["layer"], m["moves"], m["unit"], m["better"]) \
        == ("program_span", "split step", "samples_per_s", "count", "lower")
    assert m["workloads"] == ["testbed-paper", "testbed-handoff"]
