"""Whole testbed runs on the CPU at a small size: sound runs come out
correct, and each fault planted under the timed path, and the control,
come out not correct."""
from __future__ import annotations

import pytest

from _small import (SMALL, SEED, altered_codes, altered_unpack,
                    failed_checks, half_batch_step, run, scaled,
                    unchanged_step)


@pytest.mark.parametrize("workload", ["testbed-paper", "testbed-handoff"])
def test_sound_run_is_correct(workload):
    r = run(workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0
    assert r["attempted"] > 0
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"


def _patch_step(monkeypatch, breaker):
    from repro.core.scheduler import FedFlyScheduler
    build = FedFlyScheduler._build_step

    def patched(self):
        build(self)
        self._step = breaker(self._step)
    monkeypatch.setattr(FedFlyScheduler, "_build_step", patched)


def test_state_left_unchanged_is_caught(monkeypatch):
    _patch_step(monkeypatch, unchanged_step)
    r = run("testbed-handoff")
    assert not r["correct"]
    assert {"grad_gap", "update_gap"} <= set(failed_checks(r))


def test_half_batch_is_caught(monkeypatch):
    _patch_step(monkeypatch, half_batch_step)
    r = run("testbed-handoff")
    assert not r["correct"]
    assert "grad_gap" in failed_checks(r)


def test_altered_fold_is_caught(monkeypatch):
    from repro.core import fedavg
    fold = fedavg.fedavg
    monkeypatch.setattr(fedavg, "fedavg",
                        lambda trees, w: scaled(fold(trees, w), 1 + 1e-3))
    r = run("testbed-handoff")
    assert failed_checks(r) == ["fold_err"]


@pytest.mark.parametrize("workload", ["testbed-paper", "testbed-handoff"])
def test_altered_restore_is_caught(monkeypatch, workload):
    altered_unpack(monkeypatch)
    r = run(workload)
    want = "raw_mismatch" if workload == "testbed-paper" else "codec_err"
    assert want in failed_checks(r)


def test_altered_kernel_codes_are_caught(monkeypatch):
    altered_codes(monkeypatch)
    r = run("testbed-handoff")
    assert "kernel_code_off" in failed_checks(r)


@pytest.mark.parametrize("workload", ["testbed-paper", "testbed-handoff"])
def test_control_fails_a_limit(workload):
    import control
    out = control.readings(workload, SEED, 0.5, True, require_chip=False,
                           overrides=SMALL[workload])
    assert out["program"]["correct"], out
    want = {"control_step", "control_fold", "half_batch"}
    if workload == "testbed-handoff":
        want.add("control_codec")
    assert set(out["variants"]) == want
    for name, nums in out["variants"].items():
        assert not nums["correct"], (name, nums)
