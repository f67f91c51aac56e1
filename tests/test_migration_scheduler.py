"""The paper's system end-to-end: migration invariance (FedFly resume is
bit-identical to an uninterrupted run), SplitFed restart time penalty,
the ≤2 s overhead claim shape, frequent moves (Fig. 4), socket transport,
and the device-relay fallback."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.migration import MigrationExecutor
from repro.core.mobility import (MobilityTrace, move_at_round,
                                 periodic_moves, poisson_moves)
from repro.core.checkpoint import EdgeCheckpoint
from repro.core.scheduler import FedFlyScheduler
from repro.data.datasets import synthetic_cifar10
from repro.data.loader import Batcher
from repro.data.partition import balanced, by_fraction
from repro.models.vgg import VGG5
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant
from repro.runtime.cluster import (WIFI_75MBPS, make_testbed_devices,
                                   make_testbed_edges)
from repro.runtime.transport import LinkModel, SocketTransport


def make_sched(batchers, codec="raw", seed=0):
    model = VGG5()
    sched = FedFlyScheduler(
        model, sgd(momentum=0.9), make_testbed_devices(batchers),
        make_testbed_edges(), split_point=2, lr_schedule=constant(0.01),
        link=WIFI_75MBPS, migration_codec=codec, seed=seed)
    sched.initialize()
    return sched


@pytest.fixture(scope="module")
def small_batchers():
    train, _ = synthetic_cifar10(n_train=1200, n_test=100)
    return [Batcher(p, 100) for p in balanced(train, 4)]


def _params_equal(a, b):
    return all(bool(jnp.array_equal(x, y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_migration_invariance(small_batchers):
    """FedFly resume must be BIT-IDENTICAL to never moving (checkpoint is
    exact; the destination replays the same remaining batches)."""
    trace = MobilityTrace(move_at_round("pi3_1", "edge-A", "edge-B", 1, 0.5))
    s1 = make_sched(small_batchers)
    s1.run(3, trace, mode="fedfly")
    s2 = make_sched(small_batchers)
    s2.run(3, None)
    assert _params_equal(s1.global_params, s2.global_params)
    assert len(s1.migrator.reports) == 1


def test_splitfed_restart_costs_time(small_batchers):
    """Paper Fig. 3: restarting at fraction f costs ~(1+f)x the round."""
    trace = MobilityTrace(move_at_round("pi3_1", "edge-A", "edge-B", 1, 0.5))
    s_fly = make_sched(small_batchers)
    h_fly = s_fly.run(2, trace, mode="fedfly")
    s_sf = make_sched(small_batchers)
    h_sf = s_sf.run(2, trace, mode="splitfed")
    t_fly = h_fly.rounds[1].client_times_sim["pi3_1"]
    t_sf = h_sf.rounds[1].client_times_sim["pi3_1"]
    t_base = h_fly.rounds[0].client_times_sim["pi3_1"]
    assert t_sf > t_fly                      # FedFly always wins
    # restart ≈ (1+f)·T; resume ≈ T + small overhead
    assert t_sf / t_base == pytest.approx(1.5, rel=0.25)
    assert t_fly / t_base == pytest.approx(1.0, rel=0.25)


def test_migration_overhead_small(small_batchers):
    """Paper §V.C: overhead (checkpoint transfer) ≤ 2 s on the testbed
    link for a VGG-5-scale server stage."""
    trace = MobilityTrace(move_at_round("pi3_1", "edge-A", "edge-B", 0, 0.5))
    s = make_sched(small_batchers)
    s.run(1, trace, mode="fedfly")
    rep = s.migrator.reports[0]
    assert rep.sim_total_s <= 2.0
    assert rep.nbytes < 20e6


def test_int8_codec_shrinks_payload(small_batchers):
    trace = MobilityTrace(move_at_round("pi3_1", "edge-A", "edge-B", 0, 0.5))
    s_raw = make_sched(small_batchers, codec="raw")
    s_raw.run(1, trace, mode="fedfly")
    s_q = make_sched(small_batchers, codec="int8")
    s_q.run(1, trace, mode="fedfly")
    assert s_q.migrator.reports[0].nbytes < \
        s_raw.migrator.reports[0].nbytes / 3
    assert s_q.migrator.reports[0].quant_error > 0


def test_frequent_moves_preserve_training(small_batchers):
    """Paper Fig. 4 shape: moving every round must not corrupt training;
    the loss after several rounds matches the no-move run closely."""
    events = periodic_moves("pi4_1", ("edge-A", "edge-B"), 4, 1,
                            fraction=0.3)
    s1 = make_sched(small_batchers)
    h1 = s1.run(4, MobilityTrace(events), mode="fedfly")
    s2 = make_sched(small_batchers)
    h2 = s2.run(4, None)
    assert _params_equal(s1.global_params, s2.global_params)
    assert len(s1.migrator.reports) == 3


def test_device_relay_doubles_transfer_time():
    ck = EdgeCheckpoint("c", 0, 0, 0, 1,
                        {"w": np.ones((64, 64), np.float32)},
                        {"mu": np.zeros((64, 64), np.float32)})
    link = LinkModel(bandwidth_bps=75e6, latency_s=0.005)
    ex = MigrationExecutor(link=link)
    _, direct = ex.migrate(ck, "A", "B", route="direct")
    _, relay = ex.migrate(ck, "A", "B", route="device_relay")
    assert relay.sim_transfer_s == pytest.approx(
        2 * direct.sim_transfer_s, rel=1e-6)


def test_socket_transport_migration():
    """The paper ships checkpoints 'via a socket' — run a real TCP
    transfer through localhost."""
    srv = SocketTransport().serve()
    ck = EdgeCheckpoint("pi3_1", 5, 1, 2, 2,
                        {"w": np.arange(256, dtype=np.float32)},
                        {"mu": np.zeros(256, np.float32)})
    ex = MigrationExecutor(
        send=lambda dst, payload: srv.send_to("127.0.0.1", srv.port,
                                              payload),
        recv=lambda dst: srv.recv(timeout=10))
    restored, rep = ex.migrate(ck, "edge-A", "edge-B")
    srv.close()
    assert rep.transfer_s > 0
    np.testing.assert_array_equal(restored.server_params["w"],
                                  ck.server_params["w"])


def test_poisson_trace_consistency():
    events = poisson_moves(["a", "b"], ["e1", "e2", "e3"], 50, 0.2, seed=1)
    # src of each move must equal dst of the previous move of that client
    loc = {"a": "e1", "b": "e2"}
    for e in sorted(events, key=lambda e: (e.round_idx, e.client_id)):
        assert e.src_edge == loc[e.client_id]
        assert e.dst_edge != e.src_edge
        loc[e.client_id] = e.dst_edge


def test_losses_decrease(small_batchers):
    s = make_sched(small_batchers)
    h = s.run(4, None)
    first = np.mean(list(h.rounds[0].client_losses.values()))
    last = np.mean(list(h.rounds[-1].client_losses.values()))
    assert last < first


def test_delta_migrations_reuse_the_codec_programs(monkeypatch):
    """Two delta-codec moves of one checkpoint through the Pallas codec
    build its quantize and dequantize programs once, on the first move,
    and the second move restores bit-equal to the first."""
    from repro.kernels.int8_codec import int8_codec, ops
    from repro.obs import telemetry as obs

    def on_pallas(fn):
        return lambda *a, **kw: fn(*a, **{**kw, "use_pallas": True,
                                          "interpret": True})
    monkeypatch.setattr(ops, "quantize_leaves", on_pallas(ops.quantize_leaves))
    monkeypatch.setattr(ops, "dequantize_leaves",
                        on_pallas(ops.dequantize_leaves))
    for core in (int8_codec._quantize_tiles, int8_codec._dequantize_tiles):
        core.clear_cache()
    rng = np.random.default_rng(0)
    params = {"w": rng.normal(size=(3000,)).astype(np.float32),
              "b": rng.normal(size=(96,)).astype(np.float32)}
    ck = EdgeCheckpoint(
        client_id="pi3_1", round_idx=1, epoch=1, batch_idx=4, split_point=2,
        server_params=params,
        optimizer_state={"mu": {k: v * 0.1 for k, v in params.items()}})
    base = {"server_params": {k: v + np.float32(1e-3)
                              for k, v in params.items()}}
    ex = MigrationExecutor(codec="delta")
    builds, restored = [], []
    obs.enable()
    try:
        for _ in range(2):
            out, _ = ex.migrate(ck, "edge-A", "edge-B", base=base,
                                base_version="r1")
            restored.append(out)
            snap = obs.snapshot()
            builds.append(snap["counters"].get("codec.kernel_builds", 0))
    finally:
        obs.disable()
    assert builds == [2, 0]          # one quantize + one dequantize program
    first, second = (jax.tree.leaves((r.server_params, r.optimizer_state))
                     for r in restored)
    assert all(np.array_equal(a, b) for a, b in zip(first, second))
    err = np.abs(restored[0].server_params["w"] - params["w"]).max()
    assert err <= 1e-3 / 127 * 0.51 + 1e-7
