"""Where the scheduler reads a step's loss back to the host: before a
move, on the first batch trained after it, and at the epoch's end, and
nowhere else. The read ends before the cost of the batch after a move
is taken, which is where the benchmark ends the migration stall, and
the numbers equal those of a loop that reads every batch's loss."""
from __future__ import annotations

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fedavg as fedavg_lib
from repro.core import split as split_lib
from repro.core.mobility import MobilityTrace, move_at_round
from repro.core.scheduler import FedFlyScheduler
from repro.data.datasets import synthetic_cifar10
from repro.data.loader import Batcher
from repro.data.partition import balanced
from repro.models.vgg import VGG5
from repro.obs import telemetry as obs
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant
from repro.runtime.cluster import (WIFI_75MBPS, make_testbed_devices,
                                   make_testbed_edges)

BATCH, LR = 10, 0.01


@pytest.fixture(autouse=True)
def _telemetry_off_after():
    yield
    obs.disable()


@pytest.fixture(scope="module")
def data():
    train, _ = synthetic_cifar10(n_train=8 * BATCH, n_test=10)
    return train


def _batchers(data, clients, batches):
    parts = balanced(data, 8 // batches)[:clients]
    return [Batcher(p, BATCH) for p in parts]


def _sched(batchers):
    sched = FedFlyScheduler(
        VGG5(), sgd(momentum=0.9), make_testbed_devices(batchers),
        make_testbed_edges(), split_point=2, lr_schedule=constant(LR),
        link=WIFI_75MBPS, seed=0)
    sched.initialize()
    return sched


def _trace(fraction):
    if fraction is None:
        return None
    return MobilityTrace(move_at_round("pi3_1", "edge-A", "edge-B", 0,
                                       fraction))


def _spans():
    ev = obs.snapshot()["events"]
    names = [ev["names"][int(i)] for i in ev["name_idx"]]
    return [(n, int(t0), int(t0 + d))
            for n, t0, d in zip(names, ev["t0_ns"], ev["dur_ns"])]


@pytest.mark.parametrize("mode, fraction, batches, steps, reads", [
    # no move: the epoch's end only
    ("fedfly", None, 4, 4, 1),
    # resume at batch 2: before the move, batch 2, batch 3
    ("fedfly", 0.5, 4, 4, 3),
    # resume at batch 3, the epoch's last: before the move, batch 3
    ("fedfly", 0.75, 4, 4, 2),
    # a move before any batch has nothing to read first
    ("fedfly", 0.0, 4, 4, 2),
    # restart after two batches: before the move, batch 0, batch 3
    ("splitfed", 0.5, 4, 6, 3),
    # restart of a one-batch epoch: batch 0 is also the epoch's last
    ("splitfed", 0.5, 1, 1, 1),
])
def test_loss_is_read_back_only_at_sync_points(data, mode, fraction,
                                               batches, steps, reads):
    sched = _sched(_batchers(data, 1, batches))
    obs.enable()
    rec = sched.run_round(0, _trace(fraction), mode)
    counts = Counter(n for n, *_ in _spans())
    assert counts["sched.dispatch"] == steps
    assert counts["sched.cost"] == steps
    assert counts["sched.readback"] == reads
    assert counts["sched.move"] == (fraction is not None)
    assert np.isfinite(rec.client_losses["pi3_1"])


@pytest.mark.parametrize("mode", ["fedfly", "splitfed"])
def test_reads_bracket_the_move(data, mode):
    sched = _sched(_batchers(data, 1, 4))
    obs.enable()
    sched.run_round(0, _trace(0.5), mode)
    spans = _spans()
    [(_, m0, m1)] = [s for s in spans if s[0] == "sched.move"]
    reads = [(t0, t1) for n, t0, t1 in spans if n == "sched.readback"]
    # the last step before the move is read before the move begins ...
    last_dispatch = max(t1 for n, _, t1 in spans
                        if n == "sched.dispatch" and t1 <= m0)
    assert any(last_dispatch <= t0 and t1 <= m0 for t0, t1 in reads)
    # ... and the first batch after it before its cost is taken
    first_cost = min(t0 for n, t0, _ in spans
                     if n == "sched.cost" and t0 >= m1)
    between = [(t0, t1) for t0, t1 in reads if m1 <= t0 < first_cost]
    assert len(between) == 1 and between[0][1] <= first_cost


def _hand_round(sched):
    """One round as a loop that reads every batch's loss back, through
    the scheduler's own step."""
    lr = jnp.float32(LR)
    losses, trees, weights = {}, [], []
    for dev in sched.devices.values():
        st = sched.edges[dev.edge_id].clients[dev.client_id]
        for b in range(dev.batcher.num_batches):
            batch = {k: jnp.asarray(v)
                     for k, v in dev.batcher.batch_at(0, b).items()}
            (dev.dev_params, st.srv_params, dev.dev_opt, st.srv_opt,
             loss, _) = sched._step(dev.dev_params, st.srv_params,
                                    dev.dev_opt, st.srv_opt, batch, lr)
            losses[dev.client_id] = float(loss)
        trees.append(split_lib.merge_params(sched.model, dev.dev_params,
                                            st.srv_params))
        weights.append(dev.num_samples)
    return losses, fedavg_lib.fedavg(trees, weights)


@pytest.mark.parametrize("fraction", [None, 0.5])
def test_round_is_bit_identical_to_reading_every_loss(data, fraction):
    # a raw-codec FedFly move resumes bit-identically, so the hand loop
    # without a move is the reference for the round with one too
    batchers = _batchers(data, 2, 4)
    sched = _sched(batchers)
    rec = sched.run_round(0, _trace(fraction), "fedfly")
    losses, global_params = _hand_round(_sched(batchers))
    assert rec.client_losses == losses
    for a, b in zip(jax.tree.leaves(sched.global_params),
                    jax.tree.leaves(global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
