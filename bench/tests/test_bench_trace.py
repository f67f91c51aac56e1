"""The trace reduction and the metric arithmetic, against hand-computed
values: first on intervals built here, then on a small trace recorded
on one TPU v5e (a 12 ms slice of testbed-handoff: four runs of the
split step)."""
from __future__ import annotations

import gzip
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import trace_reduce  # noqa: E402

RECORDED = BENCH / "tests" / "data" / "testbed-handoff.xplane.pb.gz"
PEAK = {"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11}


def _raw():
    # one device: the step program twice (ops 0-2 ms and 5-6 ms), the
    # quantize kernel once (8.0-8.5 ms), a last op at 9.5-10 ms; host
    # spans around them, the first one starting before the device trace
    ms = 1e-3
    ops = [(0.0, 1 * ms, "fusion.1"), (1 * ms, 2 * ms, "convolution.2"),
           (5 * ms, 6 * ms, "fusion.1"),
           (8 * ms, 8.5 * ms, "%q.1 = (s8[512,1024]{1,0}, f32[512,1]{1,0}) "
            "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\""),
           (9.5 * ms, 10 * ms, "copy.3")]
    modules = [(0.0, 2 * ms, "jit_step(7)"), (5 * ms, 6 * ms, "jit_step(7)"),
               (8 * ms, 8.5 * ms, "jit__pallas(3)")]
    spans = [(-5 * ms, 10 * ms, "engine"), (2 * ms, 5 * ms, "batch"),
             (6 * ms, 8 * ms, "migrate"), (8.5 * ms, 10 * ms, "aggregate")]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": spans}


def test_busy_idle_and_labelled_gaps():
    r = trace_reduce.reduce(_raw())
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["busy_s"] == pytest.approx(4e-3)
    assert r["modules"]["jit_step"] == [2, pytest.approx(3e-3)]
    assert r["ops"]["fusion.1"] == pytest.approx(2e-3)
    assert r["op_counts"]["fusion.1"] == 2
    labels = [(name, pytest.approx(t)) for name, t in r["gaps"]]
    assert labels == [("batch", 3e-3), ("migrate", 2e-3),
                      ("aggregate", 1e-3)]
    read = harness.metric_reader("device.idle_share")
    assert read({"trace": r}) == pytest.approx(60.0)


def test_host_spans_are_put_on_the_trace_clock():
    raw = _raw()
    raw["devices"]["/device:TPU:0"]["modules"].append(
        (1.0, 1.001, "jit_bench_marker(5)"))
    # the marker was called at host time 10.0 s and returned at 10.003 s
    spans = trace_reduce.on_trace_clock(
        raw, "jit_bench_marker", (10.0, 10.003),
        [(10.5, 10.6, "batch"), (11.0, 11.5, "step")])
    assert [(pytest.approx(a), pytest.approx(b), n) for a, b, n in spans] \
        == [(1.499, 1.599, "batch"), (1.999, 2.499, "step")]
    assert trace_reduce.on_trace_clock(_raw(), "jit_bench_marker",
                                       (10.0, 10.003), spans) == []


def test_step_time_mfu_and_roofline_arithmetic():
    r = trace_reduce.reduce(_raw())
    ctx = {"trace": r, "peak": PEAK, "step_program": "jit_step",
           "rest_samples_per_s": 20_000.0,
           "train_flops_per_sample": 48_569_856,
           "obs": {"mig.quantize": [(1e-3, {"n": 520_000})]}}
    assert harness.metric_reader("split_step.device_ms")(ctx) \
        == pytest.approx(1.5)
    # 20,000 samples/s x 48,569,856 / 197 TFLOP/s
    assert harness.metric_reader("split_step.mfu")(ctx) == pytest.approx(
        100 * 20_000 * 48_569_856 / 1.97e14)
    # 520,000 floats pad to 64 tiles of 8 x 1024 = 524,288: read x and
    # base (4 B each), write codes (1 B) and 512 scales (4 B)
    moved = 524_288 * 9 + 512 * 4
    assert harness.metric_reader("int8_quantize_roofline")(ctx) \
        == pytest.approx(100 * moved / 8.19e11 / 0.5e-3)


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = trace_reduce.reduce({"devices": {}, "spans": []})
    ctx = {"trace": empty, "peak": PEAK, "step_program": "jit_step",
           "rest_samples_per_s": None, "train_flops_per_sample": 1.0,
           "obs": {}, "spans": {}}
    for name in ("split_step.device_ms", "split_step.mfu",
                 "int8_quantize_roofline", "device.idle_share",
                 "migration.pack_ms", "input.batch_ms", "fedavg.fold_ms"):
        assert harness.metric_reader(name)(ctx) is None, name


def test_recorded_chip_trace(tmp_path):
    path = tmp_path / "slice.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    raw = trace_reduce.read(str(path))
    assert raw["spans"] == []
    r = trace_reduce.reduce(raw)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"] < 0.05
    runs, seconds = r["modules"]["jit_step"]
    assert runs == 4
    # the step program's runs and the union of its ops agree to a few %
    assert 0.9 * seconds < r["busy_s"] < 1.05 * seconds
    ms = harness.metric_reader("split_step.device_ms")({
        "trace": r, "step_program": "jit_step"})
    assert 0.15 < ms < 0.18
    assert r["gaps"] and {name for name, _ in r["gaps"]} == {"other"}
