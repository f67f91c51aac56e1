"""The scheduler's own telemetry: one span of each kind per training
step (the loss's read-back only at the protocol's sync points) and per
move, nested where the docs say; no effect on the numbers;
compiles recorded as spans on the span clock; a benchmark window's
worth of events kept without drops."""
from __future__ import annotations

import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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

BATCH, PER_CLIENT = 10, 40          # two clients of four batches each


@pytest.fixture(autouse=True)
def _telemetry_off_after():
    yield
    obs.disable()


@pytest.fixture(scope="module")
def batchers():
    train, _ = synthetic_cifar10(n_train=2 * PER_CLIENT, n_test=10)
    return [Batcher(p, BATCH) for p in balanced(train, 2)]


def _run_round(batchers):
    sched = FedFlyScheduler(
        VGG5(), sgd(momentum=0.9), make_testbed_devices(batchers),
        make_testbed_edges(), split_point=2, lr_schedule=constant(0.01),
        link=WIFI_75MBPS, migration_codec="delta", seed=0)
    sched.initialize()
    trace = MobilityTrace(move_at_round("pi3_1", "edge-A", "edge-B", 0, 0.5))
    return sched, sched.run_round(0, trace)


def _spans(snap):
    ev = snap["events"]
    names = [ev["names"][int(i)] for i in ev["name_idx"]]
    return [(n, int(t0), int(t0 + d), ev["attrs"].get(str(i), {}))
            for i, (n, t0, d) in enumerate(zip(names, ev["t0_ns"],
                                               ev["dur_ns"]))]


def test_scheduler_spans_per_step_round_and_move(batchers):
    obs.enable()
    sched, rec = _run_round(batchers)
    snap = obs.snapshot()
    spans = _spans(snap)
    counts = Counter(n for n, *_ in spans)
    steps = sum(b.num_batches for b in batchers)
    assert steps == 2 * PER_CLIENT // BATCH
    for name in ("sched.put", "sched.dispatch", "sched.cost"):
        assert counts[name] == steps, name
    # the loss is read back at the protocol's sync points only: pi3_1
    # before its move, on the first batch after it and at the epoch's
    # end; pi3_2 at the epoch's end
    assert counts["sched.readback"] == 4
    assert len(rec.migrations) == 1
    for name in ("sched.move", "mig.pack", "mig.fetch", "mig.unpack",
                 "sched.restore"):
        assert counts[name] == 1, name
    assert snap["dropped"] == 0

    by_name = {n: (t0, t1, a) for n, t0, t1, a in spans}
    # the checkpoint's device-to-host copy runs inside the pack, the
    # whole migration inside the move
    for inner, outer in (("mig.fetch", "mig.pack"),
                         ("mig.pack", "sched.move"),
                         ("mig.unpack", "sched.move"),
                         ("sched.restore", "sched.move")):
        assert by_name[outer][0] <= by_name[inner][0] \
            <= by_name[inner][1] <= by_name[outer][1], (inner, outer)
    assert by_name["sched.move"][2] == {"client": "pi3_1"}
    # server-stage parameters, momentum, last gradients and the int32
    # step counter of SGD's state
    stage = jax.tree.leaves(sched.edges["edge-B"].clients["pi3_1"]
                            .srv_params)
    assert by_name["mig.fetch"][2]["bytes"] == 3 * sum(
        x.nbytes for x in stage) + 4
    assert snap["counters"] == {}


def test_telemetry_leaves_the_numbers_bit_identical(batchers):
    obs.disable()
    off_sched, off = _run_round(batchers)
    assert obs.snapshot() is None
    obs.enable()
    on_sched, on = _run_round(batchers)
    assert obs.snapshot() is not None
    assert on.client_losses == off.client_losses
    for a, b in zip(jax.tree.leaves(on_sched.global_params),
                    jax.tree.leaves(off_sched.global_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_fresh_jit_records_one_compile():
    x = jnp.arange(7.0)

    def telemetry_probe_fn(v):
        return jnp.sin(v) * 3.0

    obs.enable()
    jax.jit(telemetry_probe_fn)(x).block_until_ready()
    snap = obs.snapshot()
    compiles = [(t0, t1, a) for n, t0, t1, a in _spans(snap)
                if n == "jit.compile"
                and "telemetry_probe_fn" in a["fun"]]
    assert len(compiles) == 1
    t0, t1, _ = compiles[0]
    # put on the span clock: inside the snapshot's reading, not unix time
    assert 0 <= t0 <= t1 <= snap["clock"]["mono_ns"]


def test_compiles_reach_every_telemetry_user_once():
    # the listener is the plane's own, registered by enable(): no
    # scheduler is needed, and enabling again adds no second listener
    x = jnp.arange(5.0)

    def telemetry_probe_twice(v):
        return jnp.cos(v) + 1.0

    obs.enable()
    obs.enable()
    jax.jit(telemetry_probe_twice)(x).block_until_ready()
    funs = [a["fun"] for n, _, _, a in _spans(obs.snapshot())
            if n == "jit.compile"]
    assert sum("telemetry_probe_twice" in f for f in funs) == 1


def test_nothing_is_recorded_while_off():
    obs.enable()
    obs.disable()
    obs.record("jit.compile", 0, 1, fun="f")
    jax.jit(lambda v: v * 5.0)(jnp.arange(3.0)).block_until_ready()
    assert obs.snapshot() is None


def test_from_wall_puts_a_wall_reading_on_the_span_clock():
    mono0 = time.monotonic_ns()
    t = obs.from_wall(time.time())
    mono1 = time.monotonic_ns()
    # the same instant, read on both clocks
    assert mono0 - 1_000_000 <= t <= mono1 + 1_000_000
    # a wall reading one second earlier lands one second earlier
    assert obs.from_wall(time.time() - 1.0) == pytest.approx(
        time.monotonic_ns() - 1e9, abs=5e6)


def test_a_recorded_span_keeps_its_bounds():
    obs.enable()
    obs.record("jit.compile", 1_000, 250, fun="f")
    [(n, t0, t1, a)] = _spans(obs.snapshot())
    assert (n, t0, t1, a) == ("jit.compile", 1_000, 1_250, {"fun": "f"})


def test_a_benchmark_window_of_spans_is_kept_whole():
    # a 51 s testbed window: ~13k steps of four spans, with room over
    steps = 25_000
    obs.enable()
    for _ in range(steps):
        for name in ("sched.put", "sched.dispatch", "sched.readback",
                     "sched.cost"):
            with obs.span(name):
                pass
    snap = obs.snapshot()
    assert snap["dropped"] == 0
    assert len(snap["events"]["name_idx"]) == 4 * steps
