"""Fleet-scale FedFly: 1000 devices, 8 edge servers, Poisson mobility,
asynchronous staleness-weighted aggregation — in seconds on a laptop CPU.

The sharded discrete-event simulator (repro.sim) partitions the event
queue by edge into shard engines (edges only interact through backhaul
transfers) coordinated by a conservative lookahead window, while the
coordinator replays epoch starts and update arrivals in global time
order: cohort-vectorized vmap training keeps the JAX cost at
O(replicas), and whole flush-windows of FedAsync updates fold into the
global model in ONE exact int64 host fold (``coeff_fold_tree``) instead
of one tree-map per update. Per-round metrics are bit-identical for any
shard count.

With FLEET_SIM_WORKERS set, the shard-group worker processes own the
cohort XLA training too (the coordinator only aggregates and
broadcasts); FLEET_SIM_COHORTS>1 creates the many-cohort regime where
that parallelism shows up in the wall clock.

  PYTHONPATH=src python examples/fleet_sim.py              # 4 shards
  FLEET_SIM_SHARDS=1 PYTHONPATH=src python examples/fleet_sim.py
  FLEET_SIM_WORKERS=4 FLEET_SIM_COHORTS=8 PYTHONPATH=src \
      python examples/fleet_sim.py
"""
import json
import os
import time

from repro.core.mobility import MobilityTrace, poisson_moves
from repro.models.vgg import VGG5
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant
from repro.sim import (Fleet, FleetSimulator, hinge_staleness, make_edges,
                       make_fleet_specs)

NUM_CLIENTS = 1000
NUM_EDGES = 8
ROUNDS = 3
SHARDS = int(os.environ.get("FLEET_SIM_SHARDS", "4"))
WORKERS = int(os.environ.get("FLEET_SIM_WORKERS", "0")) or None
COHORTS = int(os.environ.get("FLEET_SIM_COHORTS", "1"))


def main():
    t0 = time.time()

    # 1. the fleet: 1000 heterogeneous devices (Pi3/Pi4 mix) on 8 edges,
    #    each training 2 batches of 16 per local epoch at split point SP2
    edges = make_edges(NUM_EDGES, slots=64)
    specs = make_fleet_specs(NUM_CLIENTS, [e.edge_id for e in edges],
                             batch_size=16, num_batches=2,
                             cohorts=COHORTS)
    fleet = Fleet(VGG5(), sgd(momentum=0.9), specs, split_point=2,
                  lr_schedule=constant(0.01), max_replicas=4, seed=0)

    # 2. Poisson mobility: ~5% of the fleet hands off every round
    trace = MobilityTrace(poisson_moves([s.client_id for s in specs],
                                        [e.edge_id for e in edges],
                                        total_rounds=ROUNDS,
                                        rate_per_round=0.05, seed=0))

    # 3. FedAsync aggregation: updates buffer per flush window and mix in
    #    with one exact host fold (int64 numpy), discounted by staleness —
    #    mid-migration devices contribute late instead of stalling a
    #    barrier. Staleness counts aggregator versions, and every fleet
    #    round applies ~NUM_CLIENTS of them, so the hinge tolerates up to
    #    two rounds of lag before discounting.
    sim = FleetSimulator(fleet, edges, trace=trace, mode="async", alpha=0.6,
                         staleness_fn=hinge_staleness(a=4.0 / NUM_CLIENTS,
                                                      b=2.0 * NUM_CLIENTS),
                         shards=SHARDS, workers=WORKERS,
                         measure_pack=WORKERS is None)
    result = sim.run(ROUNDS)
    wall = time.time() - t0

    es = result.engine_stats
    print(f"simulated {NUM_CLIENTS} devices x {ROUNDS} rounds on "
          f"{NUM_EDGES} edges / {es['num_shards']} shards in {wall:.1f}s "
          f"wall ({es['events_processed']} events, "
          f"{es['events_per_sec']:.0f} ev/s, "
          f"{es.get('windows', 1)} windows)")
    print(f"simulated clock: {es['sim_time_s']:.1f}s")
    for r in result.rounds:
        print(f"  round {r['round_idx']}: {r['n_updates']} updates "
              f"({r['n_stale']} stale, max staleness {r['max_staleness']}), "
              f"loss {r['mean_loss']:.3f}, "
              f"round time {r['mean_round_time_s']:.2f}s "
              f"(p95 {r['p95_round_time_s']:.2f}s)")
    m = result.migration_summary
    print(f"migrations: {m['count']} handoffs, "
          f"mean overhead {m['mean_overhead_s']*1e3:.0f} ms, "
          f"p95 {m.get('p95_overhead_s', 0)*1e3:.0f} ms "
          f"(queueing {m['total_queue_s']:.2f}s total), "
          f"{m['total_bytes']/1e6:.0f} MB moved")
    print(json.dumps(result.summary()))

    assert wall < 120, f"fleet sim blew the CI budget: {wall:.1f}s"
    assert all(r["n_updates"] == NUM_CLIENTS for r in result.rounds)


if __name__ == "__main__":        # spawn-safe: workers re-import this file
    main()
