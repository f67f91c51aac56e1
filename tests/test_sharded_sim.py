"""Sharded fleet simulation: shard-count invariance, worker-process
parity, congestion re-pricing, empty-round robustness, batched async
mixing equivalence."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core.mobility import MobilityTrace, MoveEvent, poisson_moves
from repro.models.vgg import VGG5
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant
from repro.runtime.cluster import HardwareProfile
from repro.sim.async_agg import AsyncAggregator, SyncAggregator
from repro.sim.edge import make_edges
from repro.sim.fleet import ClientSpec, Fleet
from repro.sim.metrics import FleetMetrics
from repro.sim.shard import InflightBatch
from repro.sim.simulator import FleetSimulator


def flat_params(tree):
    return np.concatenate([np.asarray(x).ravel()
                           for x in jax.tree.leaves(tree)])


def make_sharded(mode, shards, *, workers=None, num_clients=16,
                 num_edges=4, seed=1, rate=0.3, rounds=3, **kw):
    edges = make_edges(num_edges, slots=8)
    from repro.sim.fleet import make_fleet_specs
    specs = make_fleet_specs(num_clients, [e.edge_id for e in edges],
                             batch_size=8, num_batches=3)
    fleet = Fleet(VGG5(), sgd(momentum=0.9), specs, split_point=2,
                  lr_schedule=constant(0.01), max_replicas=4, seed=seed)
    trace = MobilityTrace(poisson_moves([s.client_id for s in specs],
                                        [e.edge_id for e in edges],
                                        rounds, rate, seed=seed))
    return FleetSimulator(fleet, edges, mode=mode, shards=shards,
                          workers=workers, trace=trace,
                          measure_pack=False, **kw)


# -- shard-count invariance --------------------------------------------------

@pytest.mark.parametrize("mode", ["sync", "async"])
def test_shard_count_invariance(mode):
    """Same seed, 1 vs 2 vs 4 shards: per-round metrics bit-identical,
    final global params bit-identical, per-edge stats identical."""
    base = make_sharded(mode, 1).run(3)
    assert base.migration_summary["count"] > 0    # migrations do cross
    for k in (2, 4):
        other = make_sharded(mode, k).run(3)
        assert other.rounds == base.rounds
        assert other.migration_summary == base.migration_summary
        assert other.edge_stats == base.edge_stats
        assert (flat_params(other.final_params)
                == flat_params(base.final_params)).all()

        def protocol_events(stats):
            # ROUND_START is a per-shard control event, one per shard per
            # round — everything else must match exactly
            return {k: v for k, v in stats["by_kind"].items()
                    if k != "round_start"}
        assert protocol_events(other.engine_stats) == \
            protocol_events(base.engine_stats)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_worker_processes_match_serial(mode):
    """The multiprocessing shard executors (windowed for sync, peer mesh
    for async) must be bit-identical to in-process shards."""
    serial = make_sharded(mode, 2, num_clients=8, rounds=2).run(2)
    mp_run = make_sharded(mode, 2, workers=2, num_clients=8,
                          rounds=2).run(2)
    assert mp_run.rounds == serial.rounds
    assert mp_run.migration_summary == serial.migration_summary
    assert (flat_params(mp_run.final_params)
            == flat_params(serial.final_params)).all()


def test_workers_require_skipping_real_pack():
    edges = make_edges(2)
    from repro.sim.fleet import make_fleet_specs
    specs = make_fleet_specs(4, [e.edge_id for e in edges])
    fleet = Fleet(VGG5(), sgd(momentum=0.9), specs, split_point=2,
                  lr_schedule=constant(0.01), max_replicas=2, seed=0)
    with pytest.raises(ValueError):
        FleetSimulator(fleet, edges, shards=2, workers=2,
                       measure_pack=True)


@pytest.mark.parametrize("executor", ["workers", "hosts"])
def test_mesh_executors_refused_on_tpu(executor, monkeypatch):
    """A TPU belongs to one process: on a TPU backend the pipe and the
    socket mesh are refused at construction, before any process exists,
    and the message names the serial executor."""
    import multiprocessing
    edges = make_edges(2)
    from repro.sim.fleet import make_fleet_specs
    specs = make_fleet_specs(4, [e.edge_id for e in edges])
    fleet = Fleet(VGG5(), sgd(momentum=0.9), specs, split_point=2,
                  lr_schedule=constant(0.01), max_replicas=2, seed=0)
    children = set(multiprocessing.active_children())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="serial executor"):
        FleetSimulator(fleet, edges, shards=2, measure_pack=False,
                       **{executor: 2})
    assert set(multiprocessing.active_children()) == children
    # the serial executor stays available on the chip
    FleetSimulator(fleet, edges, shards=2, measure_pack=False)


# -- congestion re-pricing ----------------------------------------------------

def test_inflight_batch_reprice_math():
    """Constant congestion reduces exactly to fixed + srv * g; a
    mid-flight change re-prices only the remaining server work."""
    fb = InflightBatch(client_id="c", fixed_s=1.0, srv_s=2.0,
                       remaining=3.0, last_t=0.0, cong=1.0)
    assert fb.reprice(0.0, 1.0) == pytest.approx(3.0)      # 1 + 2*1
    fb2 = InflightBatch(client_id="c", fixed_s=1.0, srv_s=2.0,
                        remaining=3.0, last_t=0.0, cong=1.0)
    assert fb2.reprice(0.0, 2.0) == pytest.approx(5.0)     # 1 + 2*2
    # halfway through (1.5 base-seconds consumed at g=1), double the load:
    # remaining 1.5 base-s now progress at rate 3/5 -> 2.5 s more
    fb3 = InflightBatch(client_id="c", fixed_s=1.0, srv_s=2.0,
                        remaining=3.0, last_t=0.0, cong=1.0)
    assert fb3.reprice(1.5, 2.0) == pytest.approx(1.5 + 1.5 / (3.0 / 5.0))


def _two_edge_fleet(trace, *, shards=1, reprice_tol=0.05):
    """Client A alone on a weak 1-slot edge-1; B on edge-0. One batch per
    epoch, so only *in-flight* re-pricing can slow A down."""
    edges = make_edges(2, slots=1,
                       profiles=(HardwareProfile("edge-tiny", 1.5e9),))
    specs = [ClientSpec(client_id="dev-A", profile=edges[0].profile,
                        edge_id="edge-1", batch_size=8, num_batches=1),
             ClientSpec(client_id="dev-B", profile=edges[0].profile,
                        edge_id="edge-0", batch_size=8, num_batches=1)]
    fleet = Fleet(VGG5(), sgd(momentum=0.9), specs, split_point=2,
                  lr_schedule=constant(0.01), max_replicas=2, seed=0)
    return FleetSimulator(fleet, edges, mode="sync", trace=trace,
                          measure_pack=False, shards=shards,
                          reprice_tol=reprice_tol)


def dur(res, cid, r=0):
    return next(c.duration_s for c in res.metrics.contributions
                if c.client_id == cid and c.round_idx == r)


def test_migrant_landing_mid_batch_repriced():
    """Regression for schedule-time-only congestion pricing: a client
    migrating onto a busy 1-slot edge mid-batch must stretch the
    resident's in-flight batch (num_batches=1, so no later batch could
    absorb the slowdown under the old model)."""
    quiet = _two_edge_fleet(None).run(1)
    trace = MobilityTrace([MoveEvent(0, "dev-B", "edge-0", "edge-1", 0.0)])
    crowded = _two_edge_fleet(trace).run(1)
    assert crowded.migration_summary["count"] == 1
    # the resident pays for the processor sharing it didn't have at
    # schedule time
    assert dur(crowded, "dev-A") > dur(quiet, "dev-A") * 1.05
    # and the re-priced run is still shard-count invariant
    crowded2 = _two_edge_fleet(trace, shards=2).run(1)
    assert crowded2.rounds == crowded.rounds
    assert dur(crowded2, "dev-A") == dur(crowded, "dev-A")


def test_reprice_tol_zero_is_at_least_as_slow():
    """Exact repricing (tol=0) can only make the crowded resident slower
    or equal vs the default tolerance band."""
    trace = MobilityTrace([MoveEvent(0, "dev-B", "edge-0", "edge-1", 0.0)])
    tol = _two_edge_fleet(trace).run(1)
    exact = _two_edge_fleet(trace, reprice_tol=0.0).run(1)
    assert dur(exact, "dev-A") >= dur(tol, "dev-A") - 1e-9


# -- empty sync round ---------------------------------------------------------

def test_empty_round_commit_carries_forward():
    """Regression: SyncAggregator.commit() used to crash on fedavg's
    non-empty assertion when every client was mid-migration/offline."""
    init = {"w": np.full((4,), 3.0, np.float32)}
    agg = SyncAggregator(init)
    out = agg.commit()                            # nothing submitted
    np.testing.assert_array_equal(out["w"], init["w"])
    assert agg.version == 1 and agg.skipped_rounds == 1
    agg.submit({"w": np.ones((4,), np.float32)}, weight=2.0)
    out = agg.commit()                            # normal rounds still work
    np.testing.assert_allclose(out["w"], 1.0)
    assert agg.version == 2 and agg.skipped_rounds == 1


def test_skipped_round_metric_record():
    m = FleetMetrics()
    m.record_skipped_round(0, 12.5)
    m.record_contribution(client_id="c", round_idx=1, arrival_s=20.0,
                          duration_s=1.0, staleness=0, loss=1.0,
                          mix_weight=0.0)
    rounds = m.build_rounds()
    assert rounds[0] == {"round_idx": 0, "n_updates": 0,
                         "skipped_round": True, "barrier_s": 12.5,
                         "n_migrations": 0}
    assert rounds[1]["round_idx"] == 1 and rounds[1]["n_updates"] == 1


# -- batched async mixing -----------------------------------------------------

def test_flush_batch_equals_sequential_submits():
    """One fedavg_agg_mix dispatch == the same updates submitted one by
    one (within fp tolerance), including the weight EMA and staleness
    discounts, and version/total_weight bookkeeping."""
    rng = np.random.default_rng(7)
    init = {"w": rng.normal(size=(300,)).astype(np.float32),
            "b": rng.normal(size=(41,)).astype(np.float32)}
    updates = [({"w": rng.normal(size=(300,)).astype(np.float32),
                 "b": rng.normal(size=(41,)).astype(np.float32)},
                float(rng.uniform(100, 900)), int(rng.integers(0, 6)))
               for _ in range(17)]
    seq = AsyncAggregator(init, alpha=0.4)
    for tree, w, s in updates:
        seq.submit(tree, weight=w, staleness=s)
    bat = AsyncAggregator(init, alpha=0.4)
    alphas = bat.flush_batch(updates)
    assert bat.version == seq.version == 17
    assert bat.total_weight_applied == pytest.approx(
        seq.total_weight_applied, rel=1e-6)
    assert len(alphas) == 17 and all(0.0 <= a <= 1.0 for a in alphas)
    np.testing.assert_allclose(bat.params["w"], seq.params["w"], atol=2e-5)
    np.testing.assert_allclose(bat.params["b"], seq.params["b"], atol=2e-5)


def test_flush_batch_groups_shared_trees():
    """Clients sharing a cohort replica share a tree object; the stacked
    axis must collapse to distinct trees without changing the math."""
    init = {"w": np.zeros((64,), np.float32)}
    shared = {"w": np.ones((64,), np.float32)}
    updates = [(shared, 100.0, 0)] * 5
    seq = AsyncAggregator(init, alpha=0.2)
    for tree, w, s in updates:
        seq.submit(tree, weight=w, staleness=s)
    bat = AsyncAggregator(init, alpha=0.2)
    bat.flush_batch(updates)
    np.testing.assert_allclose(bat.params["w"], seq.params["w"], atol=1e-6)


def test_sync_snapshots_pruned_each_round():
    """Regression: sync-mode pruning counted deduped replicas against
    the per-cohort *client* count, so the floor never advanced and every
    round's snapshots accumulated for the whole run."""
    sim = make_sharded("sync", 1, rate=0.0)
    sim.run(3)
    for cohort in sim.fleet.cohorts.values():
        assert len(cohort.snapshots) <= 1       # old epochs pruned


@pytest.mark.slow
def test_shard_sweep_cli_small_fleet(tmp_path):
    """Regression: the sweep used to mix measure_pack settings between
    shard counts at <=128 clients, tripping its own bit-identity check."""
    import json
    from benchmarks.bench_fleet import main
    artifact = tmp_path / "sweep.json"
    main(["--quick", "--shard-sweep", "1", "2", "--scenarios", "poisson",
          "--artifact", str(artifact)])
    sweep = json.loads(artifact.read_text())
    assert sweep["per_shards"]["2"]["rounds_bit_identical"] is True


def test_flush_interval_is_reproducible():
    """Explicit flush_interval_s overrides the auto grid and still gives
    deterministic, shard-invariant results."""
    a = make_sharded("async", 1, num_clients=8, rounds=2,
                     flush_interval_s=0.05).run(2)
    b = make_sharded("async", 4, num_clients=8, rounds=2,
                     flush_interval_s=0.05).run(2)
    assert a.rounds == b.rounds
    assert (flat_params(a.final_params) == flat_params(b.final_params)).all()
