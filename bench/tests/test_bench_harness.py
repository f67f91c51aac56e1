"""The benchmark's files are found by name, new cells and new
configurations need no edit to an existing file, the parts that depend
on the model come from the configuration's own files, operations are
counted from shapes, and the command refuses a machine without an
accelerator."""
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


def _bench_files(root):
    return {p: p.read_bytes() for p in (root / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def tiny_lm_checkout(tmp_path_factory):
    """A copy of the benchmark with a token model added as new files
    only, and the bytes of every file the copy had before."""
    from _small import add_token_config
    root = tmp_path_factory.mktemp("tiny-lm") / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _bench_files(root)
    assert add_token_config(root, "tiny-lm") == "tiny-lm-handoff"
    return root, before


def _run_tiny_lm(root):
    from _small import SECONDS, SEED
    import run
    return run.run_cell("tiny-lm-handoff", SEED, SECONDS, False,
                        require_chip=False, root=root)


def test_a_new_configuration_needs_no_edit_to_an_existing_file(
        tiny_lm_checkout):
    root, before = tiny_lm_checkout
    r = _run_tiny_lm(root)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert r["window"]["migrations"] >= 1
    assert r["window"]["stalls"] >= 1
    assert set(r["checks"]) == {"loss_gap", "grad_gap", "update_gap",
                                "grad_gap_f32", "update_gap_f32",
                                "fold_err", "raw_mismatch"}
    after = _bench_files(root)
    assert {p: after[p] for p in before} == before


def test_a_new_configurations_check_catches_half_a_batch(
        tiny_lm_checkout, monkeypatch):
    from _small import failed_checks, half_batch_step
    from repro.core.scheduler import FedFlyScheduler
    root, before = tiny_lm_checkout
    build = FedFlyScheduler._build_step

    def patched(self):
        build(self)
        self._step = half_batch_step(self._step)
    monkeypatch.setattr(FedFlyScheduler, "_build_step", patched)
    r = _run_tiny_lm(root)
    assert not r["correct"]
    assert {"loss_gap", "update_gap"} & set(failed_checks(r)), r["checks"]
    after = _bench_files(root)
    assert {p: after[p] for p in before} == before


def test_a_new_configurations_check_holds_no_device_array(
        tiny_lm_checkout, monkeypatch):
    import jax
    from _small import keep_cells
    root, _ = tiny_lm_checkout
    cells = keep_cells(monkeypatch)
    r = _run_tiny_lm(root)
    assert r["correct"], r["checks"]
    [cell] = cells
    held = {"capture": cell.capture.streams, "fold": cell.fold,
            "migrations": cell.migs}
    for part, tree in held.items():
        leaves = jax.tree.leaves(tree)
        assert leaves, part
        assert not [x for x in leaves if isinstance(x, jax.Array)], part
    assert r["window"]["migrations_checked"] == len(cell.migs) >= 1


def test_the_reference_counts_vgg5_training_flops():
    cfg = harness.load_json(BENCH / "configs" / "vgg5-testbed.json")
    ref = harness.reference(cfg["reference"])
    assert ref.train_flops_per_sample(cfg) == 48_569_856


def test_reference_readings_stack_every_key_of_the_batch():
    import types

    import numpy as np
    import steps
    seen = []

    def train_steps(params, batches, config, dtype, precision):
        seen.append({k: v.shape for k, v in batches[0].items()})
        loss = sum(b["frames"].sum() + b["ids"].sum() for b in batches)
        return loss[None] + params["w"][:steps.STEPS], params, params

    ref = types.SimpleNamespace(
        __name__="keys_probe", train_steps=train_steps,
        init=lambda seed, config: {"w": np.ones(4, np.float32)})
    streams = [[{"frames": np.full((6, 3), c, np.float32),
                 "ids": np.arange(6, dtype=np.int32)}
                for _ in range(steps.STEPS)] for c in range(2)]
    out = steps.reference_readings(ref, {}, 0, streams)
    steps.reference_readings(ref, {}, 0, streams, half_batch=True)
    assert seen == [{"frames": (6, 3), "ids": (6,)},
                    {"frames": (3, 3), "ids": (3,)}]
    # one row per stream: stream c's frames sum to 18c each step
    np.testing.assert_allclose(out["losses"][:, 1] - out["losses"][:, 0],
                               3 * 18)


def test_vgg5_merge_gives_the_references_leaves():
    import jax
    import numpy as np
    from repro.core import split
    from repro.models.vgg import VGG5
    cfg = harness.load_json(BENCH / "configs" / "vgg5-testbed.json")
    tr = harness.load_json(BENCH / "traffic" / "paper.json")
    cell = harness.driver("testbed").Cell(cfg, tr, 7, harness.Spans())
    model = VGG5()
    dev, srv = split.partition_params(
        model, model.init(jax.random.PRNGKey(7)), cfg["split_point"])
    merged = jax.tree.leaves(cell.merge(dev, srv))
    assert all(a is b for a, b in zip(
        merged, jax.tree.leaves(list(dev) + list(srv))))
    ref = jax.tree.leaves(harness.reference("vgg5").init(7, cfg))
    assert [x.shape for x in merged] == [x.shape for x in ref]
    for a, b in zip(merged, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


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
