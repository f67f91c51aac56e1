"""Small-size settings under which the harness's tests drive whole runs
on the CPU, and helpers to plant faults underneath the timed path."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
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
