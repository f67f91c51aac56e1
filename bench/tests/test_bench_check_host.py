"""The check keeps host data only: leaf norms in place of whole trees
give the same training numbers, a step that donates its inputs leaves
the capture whole, and the migration sample keeps to its byte budget."""
from __future__ import annotations

import numpy as np
import pytest

from _small import keep_cells, run

SHAPES = [(3, 3, 3, 32), (32,), (1024, 128), (128,), (10,)]
STREAMS = 4


def _whole_tree_numbers(prog, ref):
    """The comparison as it read whole trees: leaves stacked on a
    leading client axis, norms per client and leaf."""
    from check import NEGLIGIBLE_GRAD

    def norms(leaves):
        return np.stack([np.sqrt(np.sum(np.square(
            np.asarray(x, np.float64).reshape(x.shape[0], -1)), axis=1))
            for x in leaves], axis=1)

    def gap(p, r, keep=None):
        a, r = norms(p), norms(r)
        med = np.median(r, axis=1, keepdims=True)
        g = np.abs(a - r) / np.maximum(np.maximum(r, med), 1e-30)
        if keep is not None:
            g = np.where(keep, g, 0.0)
        return float(np.max(g))

    g_ref = norms(ref["g1"])
    keep = g_ref >= NEGLIGIBLE_GRAD * np.median(g_ref, axis=1,
                                                keepdims=True)
    return {"loss_gap": float(np.max(np.abs(prog["losses"] - ref["losses"])
                                     / np.abs(ref["losses"]))),
            "grad_gap": gap(prog["g1"], ref["g1"]),
            "update_gap": gap(prog["delta"], ref["delta"], keep)}


def _side(rng, p0, scale):
    """Stacked (R, ...) float32 first gradients and parameters after three
    steps; the last leaf's gradient is nought to rounding."""
    g1 = [(rng.normal(size=(STREAMS,) + s) * scale[i]).astype(np.float32)
          for i, s in enumerate(SHAPES)]
    g1[-1] *= np.float32(1e-9)
    p3 = [(p + 0.01 * rng.normal(size=p.shape)).astype(np.float32)
          for p in p0]
    return {"losses": rng.uniform(1.0, 3.0, (3, STREAMS)), "g1": g1,
            "p3": p3}


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 7])
def test_leaf_norms_give_the_whole_tree_numbers(seed):
    import check
    rng = np.random.default_rng(seed)
    p0 = [rng.normal(size=(STREAMS,) + s).astype(np.float32)
          for s in SHAPES]
    scale = rng.uniform(0.1, 10.0, len(SHAPES))
    prog, ref = _side(rng, p0, scale), _side(rng, p0, scale)
    prog["p3"][-1] = p0[-1] + np.float32(100.0)    # far off, and masked
    whole, by_norms = {}, {}
    for name, side in (("prog", prog), ("ref", ref)):
        whole[name] = {"losses": side["losses"], "g1": side["g1"],
                       "delta": [b.astype(np.float64) - a
                                 for a, b in zip(p0, side["p3"])]}
        by_norms[name] = {
            "losses": side["losses"],
            "g1": np.stack([check.leaf_norms(x[r] for x in side["g1"])
                            for r in range(STREAMS)]),
            "delta": np.stack([check.leaf_norms(
                np.asarray(b[r], np.float64) - a[r]
                for a, b in zip(p0, side["p3"])) for r in range(STREAMS)])}
    want = _whole_tree_numbers(whole["prog"], whole["ref"])
    assert check.training_numbers(by_norms["prog"], by_norms["ref"]) == want
    # the mask holds: the noise leaf alone would read far above the limit
    no_mask = dict(by_norms["ref"], g1=by_norms["ref"]["g1"] * 0 + 1)
    assert check.training_numbers(by_norms["prog"], no_mask)[
        "update_gap"] > 10 * want["update_gap"]


def _patch_step(monkeypatch, breaker):
    from repro.core.scheduler import FedFlyScheduler
    build = FedFlyScheduler._build_step

    def patched(self):
        build(self)
        self._step = breaker(self._step)
    monkeypatch.setattr(FedFlyScheduler, "_build_step", patched)


def test_capture_survives_a_step_that_donates_its_inputs(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.core import split
    partition = split.partition_params
    # a donating program gives each client stage arrays of its own
    monkeypatch.setattr(split, "partition_params", lambda m, p, sp: (
        jax.tree.map(jnp.copy, partition(m, p, sp))))

    def donating(step):
        def donated(*args):
            out = step(*args)
            for x in jax.tree.leaves(args[:5]):   # state trees and batch
                x.delete()
            return out
        return donated
    _patch_step(monkeypatch, donating)
    r = run("testbed-handoff")
    assert r["correct"], r["checks"]
    assert r["failed"] == 0


@pytest.mark.parametrize("budget, kept", [(1, 1), (None, 8)])
def test_migration_sample_keeps_to_its_byte_budget(monkeypatch, budget,
                                                   kept):
    import cellbase
    if budget is not None:
        monkeypatch.setattr(cellbase, "MIGRATION_SAMPLE_BYTES", budget)
    cells = keep_cells(monkeypatch)
    r = run("testbed-handoff")
    assert r["correct"], r["checks"]
    assert "codec_err" in r["checks"]
    [cell] = cells
    assert cell.mig_capacity == kept
    assert r["window"]["migrations_checked"] == len(cell.migs) \
        == min(kept, r["window"]["migrations"]) >= 1


@pytest.mark.parametrize("fail_at", [1, 60], ids=["set-up", "window"])
def test_a_failed_run_gives_the_program_its_methods_back(monkeypatch,
                                                         fail_at):
    from repro.core.checkpoint import EdgeCheckpoint
    from repro.kernels.int8_codec import ops
    to_tree, quantize = EdgeCheckpoint.to_tree, ops.quantize_leaves

    def failing(step):
        calls = []

        def failed(*args):
            calls.append(1)
            if len(calls) == fail_at:
                raise RuntimeError("planted failure")
            return step(*args)
        return failed
    _patch_step(monkeypatch, failing)
    with pytest.raises(RuntimeError, match="planted failure"):
        run("testbed-handoff")
    assert EdgeCheckpoint.to_tree is to_tree
    assert ops.quantize_leaves is quantize
