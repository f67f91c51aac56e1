"""Small-size settings under which the harness's tests drive whole runs
on the CPU, and helpers to plant faults underneath the timed path."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

SEED = 2 ** 31 + 11
SECONDS = 0.5

SMALL = {
    "testbed-paper": {"config": {"batch_size": 20},
                      "traffic": {"samples": 800}},
    "testbed-handoff": {"config": {"batch_size": 20},
                        "traffic": {"samples": 800, "moves": {
                            "clients": "all", "fraction": [0.5, 0.9]}}},
}


def run(workload: str, seed: int = SEED):
    import run as bench_run
    return bench_run.run_cell(workload, seed, SECONDS, False,
                              require_chip=False, overrides=SMALL[workload])


def failed_checks(result):
    return sorted(k for k, v in result["checks"].items()
                  if not v["value"] <= v["limit"])


def scaled(tree, factor):
    """Every float leaf times ``factor`` (an answer altered)."""
    import jax
    import numpy as np

    def f(x):
        a = np.asarray(x)
        return (a * factor).astype(a.dtype) if a.dtype.kind == "f" else x
    return jax.tree.map(f, tree)


def unchanged_step(step):
    """A step that returns its state unchanged (the loss still real)."""
    def broken(dev, srv, dev_opt, srv_opt, batch, lr):
        out = step(dev, srv, dev_opt, srv_opt, batch, lr)
        return (dev, srv, dev_opt, srv_opt) + tuple(out[4:])
    return broken


def half_batch_step(step):
    """A step that trains on the first half of its batch's rows only."""
    def broken(dev, srv, dev_opt, srv_opt, batch, lr):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return step(dev, srv, dev_opt, srv_opt, half, lr)
    return broken


def altered_codes(monkeypatch):
    """The packed quantize returns every code two steps off."""
    import numpy as np
    from repro.kernels.int8_codec import ops
    quantize = ops.quantize_leaves

    def broken(leaves, base_leaves=None, **kw):
        q, s, offsets = quantize(leaves, base_leaves, **kw)
        q = np.clip(q.astype(np.int32) + 2, -127, 127).astype(np.int8)
        return q, s, offsets
    monkeypatch.setattr(ops, "quantize_leaves", broken)


def altered_unpack(monkeypatch):
    """Every migration restores its server parameters ten per cent
    off."""
    from repro.core.checkpoint import EdgeCheckpoint
    unpack = EdgeCheckpoint.unpack.__func__

    def broken(cls, data, *, base=None):
        ck = unpack(cls, data, base=base)
        return ck.replace(server_params=scaled(ck.server_params,
                                               1.1))
    monkeypatch.setattr(EdgeCheckpoint, "unpack", classmethod(broken))


def add_token_config(root: Path, name: str) -> str:
    """Add the test-only token configuration ``tests/data/<name>/`` to
    the checkout at ``root`` as new files only, with tiny-lm's driver and
    reference, and a cell ``<name>-handoff`` in its ``BENCHMARK.json``;
    returns the cell's name."""
    cell = f"{name}-handoff"
    files = {DATA / name / "config.json": f"configs/{name}.json",
             DATA / name / "traffic.json": f"traffic/{cell}.json",
             DATA / name / "limits.json": f"limits/{cell}.json",
             DATA / "tiny-lm" / "driver.py": "drivers/tinylm.py",
             DATA / "tiny-lm" / "reference.py": "refs/tinylm.py"}
    for src, dst in files.items():
        if (root / "bench" / dst).exists():
            raise FileExistsError(f"the checkout already has {dst}")
        shutil.copy(src, root / "bench" / dst)
    config = json.loads((DATA / name / "config.json").read_text())
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": name, "source": "https://arxiv.org/abs/2403.04652",
        "file": f"bench/configs/{name}.json",
        "reduced": config["reduced"],
        "why": "test-only: a token model through FedFlyScheduler"})
    spec["workloads"].append({
        "name": cell, "config": name, "traffic": cell, "chips": 1,
        "why": "test-only: one handoff a round, raw codec"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


def keep_cells(monkeypatch):
    """The list to which every cell is added as its check starts."""
    import cellbase
    cells = []
    numbers = cellbase.CellBase.numbers

    def kept(self):
        cells.append(self)
        return numbers(self)
    monkeypatch.setattr(cellbase.CellBase, "numbers", kept)
    return cells
