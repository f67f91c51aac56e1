"""The benchmark's files are found by name, new cells need no edit to an
existing file, operations are counted from shapes, and the command
refuses a machine without an accelerator."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402
import harness  # noqa: E402


def test_vgg5_forward_flops_from_shapes():
    cfg = harness.load_json(BENCH / "configs" / "vgg5-testbed.json")
    totals = [0] + [flops.forward_per_sample(cfg["layers"][:i + 1],
                                             cfg["image"])
                    for i in range(len(cfg["layers"]))]
    per_layer = [b - a for a, b in zip(totals, totals[1:])]
    assert per_layer == [1_769_472, 9_437_184, 4_718_592, 262_144, 2_560]
    assert flops.forward_per_sample(cfg["layers"], cfg["image"]) \
        == 16_189_952
    assert flops.train_per_sample(cfg["layers"], cfg["image"]) \
        == 48_569_856


def test_every_name_in_the_spec_is_found():
    spec = harness.spec()
    for wl in spec["workloads"]:
        cell = harness.cell(wl["name"])
        cfg = cell["config"]
        assert (BENCH / "drivers" / f"{cfg['driver']}.py").exists()
        assert (BENCH / "refs" / f"{cfg['reference']}.py").exists()
        assert cell["end_to_end"] and cell["per_layer"]
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
    for m in spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    for c in spec["configs"]:
        assert (ROOT / c["file"]).exists()
        assert harness.load_json(ROOT / c["file"])["reduced"] == c["reduced"]


def test_a_new_cell_needs_no_edit_to_an_existing_file(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench" / "traffic" / "handoff-late.json").write_text(json.dumps(
        {"samples": 10000, "fractions": [0.25] * 4,
         "moves": {"clients": "all", "fraction": [0.9, 0.9]},
         "codec": "delta"}))
    (root / "bench" / "limits" / "testbed-handoff-late.json").write_text(
        (BENCH / "limits" / "testbed-handoff.json").read_text())
    (root / "bench" / "metrics" / "migration.count.py").write_text(
        "def read(ctx):\n    return len(ctx['obs'].get('mig.pack', []))\n")
    spec["workloads"].append({"name": "testbed-handoff-late",
                              "config": "vgg5-testbed",
                              "traffic": "handoff-late", "chips": 1,
                              "why": "late handoffs"})
    spec["per_layer"].append({"name": "migration.count", "unit": "count",
                              "better": "lower", "source": "program_span",
                              "layer": "migration", "moves": "stall_p95_ms",
                              "workloads": ["testbed-handoff-late"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.cell("testbed-handoff-late", root)
    assert cell["traffic"]["moves"]["fraction"] == [0.9, 0.9]
    assert [m["name"] for m in cell["per_layer"]] == ["migration.count"]
    read = harness.metric_reader("migration.count", root)
    assert read({"obs": {"mig.pack": [(0.001, {})] * 3}}) == 3
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 1.97e14
    with pytest.raises(KeyError):
        harness.peaks("TPU v0 imaginary")


def test_run_refuses_the_cpu_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "testbed-paper", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert harness.percentile(xs, 50) == 50
    assert harness.percentile(xs, 95) == 95
    assert harness.percentile([3.0], 95) == 3.0
