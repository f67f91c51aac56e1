"""The fleet simulator: FedFly protocol dynamics at 10^4-device scale.

Architecture (this is the sharded rewrite — see README.md):

  engine     — ``SimEngine`` heaps + ``ShardedEngine`` conservative-
               window coordinator (the in-process reference path)
  shard      — JAX-free per-edge ``EdgeShard`` timing engines: batch
               compute with *re-priced* congestion, moves, checkpoint
               packing, backhaul FIFOs, churn
  fleet      — cohort-vectorized client numerics (vmap over replicas)
  trainer    — WHERE the numerics run: inline on the coordinator
               (serial) or in the shard-group worker processes
               (``workers=``/``hosts=``), driven by control mail and
               shipping ``update`` records back
  mailbox    — the group mesh: pipe/socket transports, the control
               plane, and the shared coordinator drive loop
  async_agg  — sync FedAvg barrier or FedAsync *batched* staleness-
               weighted mixing (one exact host fold per flush)
  metrics    — per-round JSON records

``FleetSimulator`` is the coordinator: it partitions the edges over
``shards`` shard engines (edges only interact through backhaul
transfers, so cross-shard traffic is exactly the migrations whose
destination edge lives elsewhere), precomputes the static per-cohort
timing tables the shards need, and then *replays* the records shards
emit — epoch starts, update arrivals, migrations — in global simulated-
time order. The replay itself is pure timing + aggregation: at an epoch
start it *requests* training (from its own fleet in serial mode, from
the owning shard group's trainer otherwise, broadcasting each global-
model version at most once per group), and at an update arrival it
consumes the trained snapshot. Timing never depends on numerics, so the
replay is exact and per-round metrics are bit-identical for any shard
count, worker count, and host count (shard arithmetic is per-edge,
tie-breaks use client ids, updates ship raw/bit-exact, and training
consumes the identical broadcast bytes wherever it runs).

Aggregation: in async mode arriving updates are *buffered* and flushed
on a fixed simulated-time grid (``flush_interval_s``, default = the
fleet's fastest uncongested batch time): each flush folds the whole
window into the global model with one ``coeff_fold_tree`` call, an exact
int64 fixed-point fold in host numpy, with sequential-equivalent
effective coefficients and staleness counted against the flush
timeline. In sync mode the round barrier commits a dataset-size-weighted
average through the same host fold; an empty round carries the global
forward and is recorded as skipped instead of crashing. No Pallas kernel
runs on this path.
"""
from __future__ import annotations

import bisect
import math
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.checkpoint import EdgeCheckpoint
from repro.core.migration import MigrationExecutor
from repro.core.mobility import MobilityTrace
from repro.kernels.fedavg_agg import coeff_merge_trees, coeff_term_tree
from repro.obs import telemetry as obs
from repro.obs import trace as obs_trace
from repro.sim import agg_tree as agg_place
from repro.sim.async_agg import (AsyncAggregator, StalenessFn, SyncAggregator,
                                 poly_staleness, sync_coeffs)
from repro.sim.edge import SimEdge
from repro.sim.engine import (EventKind, Mail, SerialExecutor, ShardedEngine)
from repro.sim.faults import FaultPlan
from repro.sim.fleet import Fleet, tree_nbytes
from repro.sim.mailbox import (_BARRIER_TIMEOUT_S, GroupFailure,
                               HostShardedEngine, MultihostControl,
                               PeerShardedEngine, SocketMailbox,
                               SocketRecordSink, _dispatch_control,
                               _drive_mesh, _MeshEngineBase,
                               merge_host_finals, run_host_windows)
from repro.sim.metrics import FleetMetrics, MigrationRecord
from repro.sim import sampling as _sampling
from repro.sim.shard import EdgeShard, ShardClient, ShardEdge, batch_parts
from repro.sim.soa import SoAEdgeShard
from repro.sim.trainer import (GroupTrainer, LocalTrainer, TrainerAborted,
                               TrainerProxy)

Params = Any


@dataclass
class FleetResult:
    mode: str
    rounds: List[Dict[str, Any]]
    migration_summary: Dict[str, Any]
    engine_stats: Dict[str, Any]
    edge_stats: List[Dict[str, Any]]
    final_params: Params
    metrics: FleetMetrics
    #: merged telemetry (repro.obs.trace.summarize) — None unless the
    #: run had telemetry=True
    obs: Optional[Dict[str, Any]] = None

    def summary(self) -> Dict[str, Any]:
        timed = [r for r in self.rounds if "mean_round_time_s" in r]
        out = {
            "mode": self.mode,
            "num_rounds": len(self.rounds),
            "sim_time_s": self.engine_stats["sim_time_s"],
            "events_per_sec": self.engine_stats["events_per_sec"],
            "events_processed": self.engine_stats["events_processed"],
            "num_shards": self.engine_stats.get("num_shards", 1),
            "final_mean_loss": (timed[-1]["mean_loss"] if timed else None),
            "mean_round_time_s": float(np.mean(
                [r["mean_round_time_s"] for r in timed])) if timed else None,
            "migrations": self.migration_summary,
            "recoveries": self.engine_stats.get("recoveries", 0),
            # aggregation-plane digest (ARCHITECTURE §3.8): which tree
            # ran, what crossed into the root, where the root sat
            "agg": self.engine_stats.get("agg"),
        }
        if self.obs is not None:
            out["obs"] = self.obs
        return out


class FleetSimulator:
    """Sharded discrete-event FedFly simulation over a ``Fleet`` and
    ``SimEdge``s. ``shards=1`` (default) is the degenerate single-heap
    case; ``workers=N`` runs N shard-group processes over pipes;
    ``hosts=N`` runs N shard-group processes connected only by TCP
    sockets — the localhost harness of the multi-host protocol
    (``run_multihost`` spreads the same protocol over separate
    machines). Both support sync AND async mode (the sync round restart
    rides the coordinator→mesh control channel), both move the cohort
    XLA training into the group processes (each group owns the cohorts
    whose clients it hosts), and both require ``measure_pack=False`` —
    group timing engines price migrations from the cached cohort
    tables. On a TPU backend both are refused at construction: the chip
    belongs to this process, so only the serial executor can train on
    it."""

    def __init__(self, fleet: Fleet, edges: Sequence[SimEdge], *,
                 trace: Optional[MobilityTrace] = None,
                 mode: str = "sync",
                 alpha: float = 0.6,
                 staleness_fn: Optional[StalenessFn] = None,
                 dropouts: Optional[Dict[str, Tuple[int, float]]] = None,
                 migration_codec: str = "raw",
                 measure_pack: bool = True,
                 shards: int = 1,
                 workers: Optional[int] = None,
                 hosts: Optional[int] = None,
                 flush_interval_s: Optional[float] = None,
                 reprice_tol: float = 0.05,
                 telemetry: bool = False,
                 trace_path: Optional[str] = None,
                 recovery: bool = True,
                 max_recoveries: int = 2,
                 fault_plan: Optional[FaultPlan] = None,
                 barrier_timeout_s: Optional[float] = None,
                 control_timeout_s: Optional[float] = None,
                 sample_fraction: float = 1.0,
                 scheduler: str = "heap",
                 client_state: str = "objects",
                 agg_tree: str = "flat"):
        if mode not in ("sync", "async"):
            raise ValueError(f"mode must be sync|async, got {mode!r}")
        if agg_tree not in ("flat", "2level"):
            raise ValueError(f"agg_tree must be flat|2level, got "
                             f"{agg_tree!r}")
        if not 0.0 < sample_fraction <= 1.0:
            raise ValueError(f"sample_fraction must be in (0, 1], got "
                             f"{sample_fraction}")
        if sample_fraction < 1.0 and mode != "sync":
            raise ValueError("sample_fraction < 1 requires mode='sync': "
                             "async flushes have no per-round participant "
                             "set to sample")
        if scheduler not in ("heap", "calendar"):
            raise ValueError(f"scheduler must be heap|calendar, got "
                             f"{scheduler!r}")
        if client_state not in ("objects", "soa"):
            raise ValueError(f"client_state must be objects|soa, got "
                             f"{client_state!r}")
        if client_state == "soa" and measure_pack:
            raise ValueError("client_state='soa' requires "
                             "measure_pack=False: the SoA hot path prices "
                             "migrations from the cached cohort tables")
        if fault_plan is not None and workers is None and hosts is None:
            raise ValueError("fault_plan requires a mesh executor "
                             "(workers= or hosts=): the serial path has "
                             "no processes to fail")
        if dropouts and mode == "sync":
            raise ValueError("device churn (dropouts) requires mode='async'; "
                             "a sync barrier would deadlock on offline "
                             "clients")
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if workers is not None:
            if workers < 1:
                raise ValueError(f"workers must be >= 1, got {workers}")
            if measure_pack:
                raise ValueError("workers (multiprocessing shards) require "
                                 "measure_pack=False: shard processes "
                                 "price migrations from the cached cohort "
                                 "tables, not real checkpoint packs")
        if hosts is not None:
            if hosts < 1:
                raise ValueError(f"hosts must be >= 1, got {hosts}")
            if measure_pack:
                raise ValueError("hosts (socket-sharded execution) "
                                 "requires measure_pack=False: host "
                                 "processes price migrations from the "
                                 "cached cohort tables, not real "
                                 "checkpoint packs")
            if workers is not None:
                raise ValueError("hosts and workers are mutually "
                                 "exclusive (sockets vs pipes)")
        if (workers is not None or hosts is not None) \
                and jax.default_backend() == "tpu":
            # the Fleet already initialised its model here, so this
            # process holds the chip; sending the groups to the CPU
            # would hide the device instead of using it
            raise ValueError(
                f"{'workers' if workers is not None else 'hosts'}= spawns "
                "shard-group processes that train with JAX, but a TPU "
                "chip belongs to one process. Run on the serial executor "
                "(leave workers and hosts unset) on a TPU backend")
        self.fleet = fleet
        self.edge_order = [e.edge_id for e in edges]
        self.edges: Dict[str, SimEdge] = {e.edge_id: e for e in edges}
        # repro-lint: allow[deterministic-iteration] validation only —
        # raises on the first unknown edge, mutates nothing
        for c in fleet.clients.values():
            if c.edge_id not in self.edges:
                raise ValueError(f"client {c.client_id} starts on unknown "
                                 f"edge {c.edge_id}")
        self.trace = trace
        self.mode = mode
        self.dropouts = dropouts or {}
        self.measure_pack = measure_pack
        self.migrator = MigrationExecutor(codec=migration_codec)
        self.num_shards = min(shards, len(self.edge_order))
        self.workers = workers
        self.hosts = (min(hosts, self.num_shards) if hosts is not None
                      else None)
        self.flush_interval_s = flush_interval_s
        self.reprice_tol = reprice_tol
        self.sample_fraction = sample_fraction
        self.scheduler = scheduler
        self.client_state = client_state
        self.agg_tree = agg_tree
        # per-round participant accounting (sampled runs only; None
        # means every client participates every round)
        self._expected_by_round: Optional[List[int]] = None
        self._cohort_round_sizes: Optional[List[Dict[Tuple, int]]] = None
        # wall-clock observation only (docs/OBSERVABILITY.md): spans and
        # counters never read simulated time, so enabling telemetry
        # cannot perturb metrics or numerics
        self.telemetry = telemetry
        self.trace_path = trace_path
        # fault tolerance (ARCHITECTURE §3.7): with recovery on, a dead
        # shard group rebuilds the mesh over the survivors instead of
        # aborting; fault_plan injects deterministic failures; the
        # timeout knobs override the module-constant deadlines (chaos
        # tests shrink them, real deployments stretch them)
        self.recovery = recovery
        self.max_recoveries = max_recoveries
        self.fault_plan = fault_plan
        self.barrier_timeout_s = barrier_timeout_s
        self.control_timeout_s = control_timeout_s

        self.metrics = FleetMetrics()
        if mode == "sync":
            self.agg: Any = SyncAggregator(fleet.global_params)
        else:
            self.agg = AsyncAggregator(fleet.global_params, alpha=alpha,
                                       staleness_fn=staleness_fn)
        self.num_rounds = 0
        # replay state — migration transfers are priced from the ENCODED
        # payload bytes of the configured codec, so backhaul backpressure
        # (and the conservative lookahead window) reflect the compression
        self._tables = fleet.cohort_tables(codec=migration_codec)
        self._cohort_sizes = fleet.cohort_sizes()
        self._buffer: List[tuple] = []          # async: (tree, w, item)
        self._flush_times: List[float] = []     # flush timeline (times)
        self._flush_versions: List[int] = []    # cumulative version after
        self._grid_k = 0                        # last fired flush grid index
        self._round_weights: Dict[Tuple, float] = {}
        self._arrived = 0
        self._round_idx = 0
        self._round_last_arrival = 0.0
        self._consumed: Dict[Tuple, int] = {}   # (cohort, epoch) -> count
        self._prune_floor: Dict[Tuple, int] = {k: 0 for k in fleet.cohorts}
        self.coordinator: Optional[Any] = None
        # numerics engine: the serial default trains inline; the mesh
        # paths swap in a TrainerProxy over the control channel
        self._trainer: Any = LocalTrainer(fleet)
        self._mesh: Optional[_MeshEngineBase] = None
        # recovery replay state (ARCHITECTURE §3.7). The replay item
        # stream — epoch starts + contributions under the (t, priority,
        # key) total order — is independent of how windows chunk it, so
        # "skip the first ``_applied`` items" replays exactly the
        # un-applied suffix after a rebuild. Migrations are deduped by
        # record identity instead (their frontier bucketing is NOT
        # partition-stable; metrics re-sorts, so only the set matters).
        self._applied = 0                       # items applied, ever
        self._skip = 0                          # items to drop on replay
        self._seen_migs: set = set()
        # hierarchical aggregation plane (ARCHITECTURE §3.8). All of it
        # is numerics-and-reporting state: the fold algebra is partition-
        # invariant (exact int64 accumulators), and root placement is a
        # priced *decision*, never a timeline event — so none of this
        # can perturb per-round timing metrics.
        self._cohort_owner: Dict[Tuple, int] = {}
        self._owner_of_shard: Dict[int, int] = {}
        self._fold_seq = 0                      # fresh per fold exchange
        self._pending_floors: Dict[Tuple, int] = {}
        self._ingress_bytes = 0                 # bytes folded at the root
        self._root_edge: Optional[str] = None
        self._root_log: List[List[Any]] = []    # [window, edge] per place
        self._root_moves = 0
        self._root_move_bytes = 0
        #: per-round restart mail, appended at commit time — what a
        #: rebuilt sync mesh needs to be re-driven through already-
        #: committed rounds (``_mesh_catch_up``)
        self._restart_log: List[List[Mail]] = []
        #: recovery accounting, merged into engine stats on the mesh
        #: paths (None on the serial path — no processes can fail)
        self._recovery: Optional[Dict[str, Any]] = None

    # -- sampled participation ------------------------------------------

    def _prepare_sampling(self, rounds: int) -> None:
        """Precompute per-round participant counts (global + per cohort)
        with the same pure decision function the shards use
        (``repro.sim.sampling``), so the sync barrier and the snapshot
        prune floor know exactly how many contributions each round owes.
        No-op for ``sample_fraction >= 1`` — the legacy static counts
        stay in force and nothing touches the RNG."""
        if self.sample_fraction >= 1.0:
            self._expected_by_round = None
            self._cohort_round_sizes = None
            return
        ids = sorted(self.fleet.clients)
        digs = _sampling.digests_for(ids)
        ckeys = sorted({self.fleet.clients[c].spec.cohort_key for c in ids})
        cidx = {k: i for i, k in enumerate(ckeys)}
        cohort_of = np.array(
            [cidx[self.fleet.clients[c].spec.cohort_key] for c in ids])
        self._expected_by_round = []
        self._cohort_round_sizes = []
        for r in range(rounds):
            mask = _sampling.participation_mask(
                digs, self.fleet.seed, r, self.sample_fraction)
            self._expected_by_round.append(int(mask.sum()))
            counts = np.bincount(cohort_of[mask], minlength=len(ckeys))
            self._cohort_round_sizes.append(
                {k: int(counts[i]) for k, i in cidx.items() if counts[i]})

    def _round_expected(self, r: int) -> int:
        """Contributions the sync barrier waits for in round ``r``."""
        if self._expected_by_round is None:
            return self.fleet.num_clients
        return self._expected_by_round[r] if r < len(self._expected_by_round) \
            else 0

    def _round_size(self, cohort_key, epoch: int) -> Optional[int]:
        """Contributions (cohort, epoch) owes before its snapshot can be
        pruned; None caps the prune floor at the final round."""
        if self._cohort_round_sizes is None:
            return self._cohort_sizes[cohort_key]
        if epoch >= len(self._cohort_round_sizes):
            return None
        return self._cohort_round_sizes[epoch].get(cohort_key, 0)

    # -- static timing inputs -------------------------------------------

    def _min_batch_time(self) -> float:
        """Fastest uncongested batch anywhere in the fleet — the default
        async flush interval (shard-count independent by construction;
        same formula as the shards', via shard.batch_parts)."""
        dev_flops = {c.spec.profile.flops_per_s
                     for c in self.fleet.clients.values()}
        best = math.inf
        # repro-lint: allow[deterministic-iteration] pure min-reduction
        # over all (table, flops, edge) combos — order-insensitive
        for t in self._tables.values():
            for df in dev_flops:
                # repro-lint: allow[deterministic-iteration] same
                # min-reduction
                for e in self.edges.values():
                    best = min(best, sum(batch_parts(
                        t, df, e.profile.flops_per_s, e.wireless)))
        return best

    def _lookahead(self) -> float:
        """Conservative safe horizon: no cross-shard message (a backhaul
        checkpoint transfer) can be delivered sooner than this after it
        is sent. With measured packing the payload size is not known a
        priori, so only the link latency is safe."""
        lat = min(e.backhaul.latency_s for e in self.edges.values())
        if self.measure_pack:
            return lat
        min_ckpt = min(t["ckpt"] for t in self._tables.values())
        max_bw = max(e.backhaul.bandwidth_bps for e in self.edges.values())
        return lat + 8.0 * min_ckpt / max_bw

    def _pack_fn(self):
        if not self.measure_pack:
            return None
        fleet, migrator = self.fleet, self.migrator

        def pack(client_id, cohort_key, replica, epoch, batch_idx, src, dst):
            cohort = fleet.cohorts[cohort_key]
            srv, opt = cohort.server_state_for(replica)
            ckpt = EdgeCheckpoint(
                client_id=client_id, round_idx=epoch, epoch=epoch,
                batch_idx=batch_idx, split_point=fleet.sp,
                server_params=srv, optimizer_state=opt, loss=0.0,
                rng_seed=fleet.seed)
            base = (fleet.migration_base()
                    if migrator.codec == "delta" else None)
            _, report = migrator.migrate(ckpt, src, dst, base=base,
                                         base_version="global")
            return report.nbytes, report.pack_s, report.unpack_s
        return pack

    # -- shard construction ---------------------------------------------

    def _shard_of_edge(self) -> Dict[str, int]:
        return {eid: i % self.num_shards
                for i, eid in enumerate(self.edge_order)}

    def _cohort_owners(self, owner_of_shard: Dict[int, int]
                       ) -> Dict[Tuple, int]:
        """Group that owns each cohort's replica stack under worker
        training: the group of the shard hosting most of the cohort's
        clients (initial placement; ties to the lowest shard id). The
        mapping is a pure function of the fleet + shard layout, so every
        rank of a multi-host run computes the same one."""
        shard_of_edge = self._shard_of_edge()
        counts: Dict[Tuple, Dict[int, int]] = {}
        for cid in sorted(self.fleet.clients):
            c = self.fleet.clients[cid]
            per = counts.setdefault(c.spec.cohort_key, {})
            sid = shard_of_edge[c.edge_id]
            per[sid] = per.get(sid, 0) + 1
        return {key: owner_of_shard[min(per, key=lambda s: (-per[s], s))]
                for key, per in counts.items()}

    def _trainer_blobs(self, cohort_owner: Dict[Tuple, int]
                       ) -> Dict[int, bytes]:
        """Pickled ``CohortSpec`` lists per owner group — the trainer
        bootstrap payload. Kept as opaque bytes so a group that owns no
        cohorts (or never trains) never pays the JAX import."""
        specs = self.fleet.cohort_specs()
        by_group: Dict[int, list] = {}
        for key in sorted(cohort_owner):
            by_group.setdefault(cohort_owner[key], []).append(specs[key])
        # repro-lint: allow[no-pickle-on-wire] spawn bootstrap, not wire:
        # these bytes ride the trusted spawn channel into our own worker
        # and are decoded once by GroupTrainer._cohorts, never by a peer
        import pickle
        # repro-lint: allow[no-pickle-on-wire] same spawn-bootstrap blob
        return {g: pickle.dumps(lst) for g, lst in sorted(by_group.items())}

    def _build_shards(self, rounds: int) -> List[EdgeShard]:
        shard_of_edge = self._shard_of_edge()
        attached: Dict[str, int] = {eid: 0 for eid in self.edge_order}
        clients_by_shard: Dict[int, List[ShardClient]] = {
            s: [] for s in range(self.num_shards)}
        moves_of: Dict[str, Dict[int, Tuple[str, float]]] = {}
        if self.trace is not None:
            for mv in self.trace.events:      # one pass, not per (c, epoch)
                if mv.round_idx < rounds:
                    d = moves_of.setdefault(mv.client_id, {})
                    # first event wins, like MobilityTrace.move_for
                    d.setdefault(mv.round_idx, (mv.dst_edge, mv.fraction))
        for cid in sorted(self.fleet.clients):
            c = self.fleet.clients[cid]
            moves = moves_of.get(cid, {})
            attached[c.edge_id] += 1
            clients_by_shard[shard_of_edge[c.edge_id]].append(ShardClient(
                client_id=cid, cohort_key=c.spec.cohort_key,
                replica=c.replica, edge_id=c.edge_id,
                num_samples=c.spec.num_samples,
                num_batches=c.spec.num_batches,
                dev_flops_per_s=c.spec.profile.flops_per_s,
                moves=moves, dropout=self.dropouts.get(cid)))
        pack_fn = self._pack_fn()
        sampling = ((self.fleet.seed, self.sample_fraction)
                    if self.sample_fraction < 1.0 else None)
        shard_cls = SoAEdgeShard if self.client_state == "soa" else EdgeShard
        out = []
        for s in range(self.num_shards):
            sedges = [ShardEdge.from_sim_edge(self.edges[eid])
                      for eid in self.edge_order
                      if shard_of_edge[eid] == s]
            for e in sedges:
                e.attached = attached[e.edge_id]
            out.append(shard_cls(s, sedges, clients_by_shard[s],
                                 self._tables, shard_of_edge,
                                 mode=self.mode, num_rounds=rounds,
                                 pack_fn=pack_fn,
                                 reprice_tol=self.reprice_tol,
                                 sampling=sampling,
                                 scheduler=self.scheduler))
        return out

    # -- numerics replay --------------------------------------------------

    def _version_at(self, t: float) -> int:
        """Aggregator version as of simulated time t (flush timeline)."""
        i = bisect.bisect_right(self._flush_times, t)
        return self._flush_versions[i - 1] if i else 0

    def _train(self, cohort_key, epoch: int):
        """Request (cohort, epoch): trains inline in serial mode, sends
        a control-mail train directive to the owning shard group
        otherwise (broadcasting the current global version first if that
        group hasn't synced it)."""
        self._trainer.request(cohort_key, epoch)

    def _fire_flush(self, t: float):
        """Apply all buffered updates (arrival < t) in one host fold."""
        if not self._buffer:
            return
        base = self.agg.version
        updates, items = [], []
        for tree, weight, item in self._buffer:
            staleness = base - self._version_at(item["pulled_s"])
            updates.append((tree, weight, staleness))
            items.append((item, staleness))
        self._buffer.clear()
        if self.agg_tree == "2level":
            alphas = self._flush_two_level(updates, items)
        else:
            # flat ingress: one model-sized tree per *distinct* update
            # folded at the coordinator (cohort replicas shared by many
            # clients count once — they arrive once)
            uniq: Dict[int, Any] = {}
            for tree, _, _ in updates:
                uniq.setdefault(id(tree), tree)
            self._count_ingress(list(uniq.values()))
            alphas = self.agg.flush_batch(updates)
        for (item, staleness), a in zip(items, alphas):
            item["record"].staleness = staleness
            item["record"].mix_weight = a
            self._consume(item["cohort_key"], item["epoch"])
        self._flush_times.append(t)
        self._flush_versions.append(self.agg.version)
        self.fleet.set_global(self.agg.params)

    def _advance_grid(self, t: float):
        """Fire async flush grid points at or before time t."""
        if self.mode != "async":
            return
        while (self._grid_k + 1) * self._flush_dt <= t:
            self._grid_k += 1
            self._fire_flush(self._grid_k * self._flush_dt)

    # -- hierarchical aggregation (ARCHITECTURE §3.8) ---------------------

    def _count_ingress(self, trees: Sequence[Params]) -> None:
        """Account aggregation-plane bytes folded at the root: model-
        sized update trees in flat mode, ONE int64 partial per
        contributing group in two-level mode. Computed from tree sizes,
        so the counter is executor-independent (the serial path has no
        wire but folds the same trees)."""
        n = 0
        for t in trees:
            n += tree_nbytes(t)
        self._ingress_bytes += n
        obs.count("coord.ingress_bytes", n)

    def _edges_of_shard(self) -> Dict[int, List[str]]:
        out: Dict[int, List[str]] = {}
        for i, eid in enumerate(self.edge_order):
            out.setdefault(i % self.num_shards, []).append(eid)
        return out

    def _flush_two_level(self, updates: Sequence[Tuple[Any, float, int]],
                         items: Sequence[Tuple[Any, int]]) -> List[float]:
        """Async flush, two-level: the buffer holds (cohort, epoch,
        replica) references instead of trees, the owner groups fold
        their retained snapshots under the exact effective coefficients,
        and the merged partials commit through ``commit_acc`` —
        bit-identical to ``flush_batch`` (same sequential coefficients,
        same exact fold algebra, partition-invariant int64 sums).

        A group death mid-exchange restores the flush window — buffer
        contents, weight EMA, grid cursor (both callers advanced it
        immediately before this flush) — because the post-recovery
        replay skips already-applied items, so an un-restored flush
        would never re-fire and its updates would be lost."""
        saved_ema = self.agg._weight_ema
        try:
            alphas, grouped, keep = self.agg.flush_coeffs(updates)
            acc = self._exchange_partials(list(grouped.items()))
            return self.agg.commit_acc(acc, keep, alphas)
        except TrainerAborted:
            self.agg._weight_ema = saved_ema
            self._buffer = [(k, w, item) for (k, w, _), (item, _)
                            in zip(updates, items)]
            self._grid_k -= 1
            raise

    def _exchange_partials(self, per: Sequence[Tuple[Tuple, float]]
                           ) -> Optional[Params]:
        """One fold exchange: group the ((cohort, epoch, replica) ->
        exact coefficient) entries by owner group, obtain ONE int64
        partial per contributing group — folded inline from the local
        fleet's snapshots on the serial path, via ``fold`` directives +
        ``partial_agg`` records on a mesh — place the floating root,
        and return the merged accumulator. Root-side aggregation
        ingress is O(contributing groups), not O(cohort replicas)."""
        by_group: Dict[int, List[list]] = {}
        for (ck, epoch, rep), coeff in per:
            g = self._cohort_owner[ck]
            by_group.setdefault(g, []).append(
                [ck, int(epoch), int(rep), float(coeff)])
        seq = self._fold_seq
        self._fold_seq += 1
        accs: Dict[int, Params] = {}
        if isinstance(self._trainer, TrainerProxy):
            # prune floors ride the owner's fold directive (retain-mode
            # groups don't prune eagerly); floors for groups with no
            # fold this window stay pending
            floors: Dict[int, List[list]] = {}
            for ck in sorted(self._pending_floors):
                g = self._cohort_owner.get(ck)
                if g in by_group:
                    floors.setdefault(g, []).append(
                        [ck, self._pending_floors[ck]])
            for g in sorted(by_group):
                self._trainer.send_fold(g, seq, by_group[g],
                                        floors.get(g, []))
            for g in sorted(floors):
                for ck, _ in floors[g]:
                    self._pending_floors.pop(ck, None)
            payloads = self._trainer.partials_for(seq, by_group)
            from repro.runtime.serialization import unpack_pytree
            for g in sorted(payloads):
                accs[g] = unpack_pytree(payloads[g])
        else:
            for g in sorted(by_group):
                acc = None
                for ck, epoch, rep, coeff in by_group[g]:
                    tree = self.fleet.cohorts[ck].snapshots[epoch][rep]
                    term = coeff_term_tree(tree, coeff)
                    acc = (term if acc is None
                           else coeff_merge_trees([acc, term]))
                accs[g] = acc
        self._count_ingress([accs[g] for g in sorted(accs)])
        self._place_root({g: float(tree_nbytes(accs[g]))
                          for g in sorted(accs)}, seq)
        return coeff_merge_trees([accs[g] for g in sorted(accs)])

    def _place_root(self, bytes_by_group: Dict[int, float],
                    window: int) -> None:
        """Re-score the floating root over the live groups' home edges.
        A placement change is priced through the real delta-migration
        pipeline (report-only — the simulated timeline never sees it,
        keeping timing metrics bit-identical with and without a move)
        and announced to the mesh as ``agg_place`` control mail."""
        homes = agg_place.group_homes(self._owner_of_shard,
                                      self._edges_of_shard())
        links = {eid: self.edges[eid].backhaul for eid in self.edge_order}
        root, _ = agg_place.place_root(homes, bytes_by_group, links)
        if root == self._root_edge:
            return
        if self._root_edge is not None:
            moved = self._price_root_move(self._root_edge, root)
            self._root_moves += 1
            self._root_move_bytes += moved
            obs.count("agg.root_move_bytes", moved)
        self._root_edge = root
        self._root_log.append([int(window), root])
        obs.gauge("agg.root_edge", float(self.edge_order.index(root)))
        if isinstance(self._trainer, TrainerProxy):
            for g in sorted(set(self._owner_of_shard.values())):
                self._trainer.send_place(g, self._round_idx, root)

    def _price_root_move(self, src: str, dst: str) -> int:
        """Price relocating the root aggregator's state (the server-
        stage partition of the current global model) src -> dst through
        the migration pipeline — delta-encoded against the broadcast
        base every edge already holds, exactly like a client move."""
        fleet = self.fleet
        ckpt = EdgeCheckpoint(
            client_id="agg-root", round_idx=self._round_idx,
            epoch=self._round_idx, batch_idx=0, split_point=fleet.sp,
            server_params=fleet.migration_base()["server_params"],
            optimizer_state={}, loss=0.0, rng_seed=fleet.seed)
        base = (fleet.migration_base()
                if self.migrator.codec == "delta" else None)
        _, report = self.migrator.migrate(ckpt, src, dst, base=base,
                                          base_version="global")
        return int(report.nbytes)

    def _consume(self, cohort_key, epoch: int, prune: bool = True):
        """Snapshot-pruning bookkeeping: one *client's* contribution for
        (cohort, epoch) has been accounted for. Sync mode counts at
        contribution time but defers the prune to after the commit (the
        commit still reads the snapshots)."""
        key = (cohort_key, epoch)
        self._consumed[key] = self._consumed.get(key, 0) + 1
        if prune:
            self._maybe_prune(cohort_key)

    def _maybe_prune(self, cohort_key):
        floor0 = self._prune_floor[cohort_key]
        floor = floor0
        while True:
            size = self._round_size(cohort_key, floor)
            # sampled rounds owe their participant count (a zero-
            # participant round owes nothing and advances immediately);
            # the floor never passes the final round
            if size is None or self._consumed.get((cohort_key, floor),
                                                  0) < size:
                break
            floor += 1
        if floor != floor0:
            self._prune_floor[cohort_key] = floor
            # drop the fully-consumed counters with the snapshots they
            # tracked — otherwise ``_consumed`` grows one key per
            # (cohort, epoch) for the life of the run
            for e in range(floor0, floor):
                self._consumed.pop((cohort_key, e), None)
            self._trainer.prune(cohort_key, floor)
            if (self.agg_tree == "2level"
                    and isinstance(self._trainer, TrainerProxy)):
                # retain-mode groups keep snapshots for their folds, so
                # the floor rides the owner's next fold directive
                self._pending_floors[cohort_key] = floor

    def _on_window(self, bound: float,
                   all_records: Dict[int, Dict[str, list]]) -> List[Mail]:
        # migrations: timing-complete, straight into metrics. The seen-
        # set drops re-shipments from a post-recovery replay (a rebuilt
        # mesh re-runs history from t=0); records are unique in a fault-
        # free run (one move per client per round), so the no-fault path
        # records exactly what it always did.
        for rec in sorted(
                (m for r in all_records.values() for m in r["migrations"]),
                key=lambda m: (m[4], m[0])):
            ident = tuple(rec)        # wire decode may hand back a list
            if ident in self._seen_migs:
                continue
            self._seen_migs.add(ident)
            (cid, src, dst, round_idx, start_s, end_s, nbytes, pack_s,
             queue_s, transfer_s) = rec
            self.metrics.record_migration(MigrationRecord(
                client_id=cid, src_edge=src, dst_edge=dst,
                round_idx=round_idx, start_s=start_s, end_s=end_s,
                nbytes=nbytes, pack_s=pack_s, queue_s=queue_s,
                transfer_s=transfer_s))
        # merge epoch starts and contributions into one time-ordered replay
        items: List[tuple] = []
        # repro-lint: allow[deterministic-iteration] feeds items.sort()
        # below, whose (t, priority, key) key is a total tie-break — the
        # visit order here cannot reach the replay order
        for r in all_records.values():
            for t, cohort_key, epoch in r["epoch_starts"]:
                items.append((t, 1, str(cohort_key), ("start", cohort_key,
                                                      epoch)))
            for con in r["contribs"]:
                items.append((con[0], 2, con[1], ("contrib", con)))
        items.sort(key=lambda it: it[:3])

        mail: List[Mail] = []
        replay_span = obs.span("coord.window", items=len(items))
        replay_span.__enter__()
        for t, _, _, action in items:
            if self._skip:
                # applied before the failure (ARCHITECTURE §3.7): the
                # rebuilt mesh re-ships history from t=0, and the item
                # stream is a partition-independent total order, so
                # dropping the first N items replays exactly the
                # un-applied suffix. Grid flushes for them fired too —
                # the skip must come before _advance_grid.
                self._skip -= 1
                continue
            self._advance_grid(t)
            if action[0] == "start":
                self._train(action[1], action[2])
                self._applied += 1
                continue
            (arrival, cid, cohort_key, replica, epoch, epoch_start_s,
             pulled_s, num_samples) = action[1]
            # may raise TrainerAborted (owner group died): the item is
            # then NOT counted as applied and replays after recovery.
            # Two-level mode ships losses-only updates (the model trees
            # stay with the owner group for its fold), so the trees list
            # must not be indexed.
            trees, losses = self._trainer.update_for(cohort_key, epoch)
            loss = float(losses[replica])
            record = self.metrics.record_contribution(
                client_id=cid, round_idx=epoch, arrival_s=arrival,
                duration_s=arrival - epoch_start_s, staleness=0,
                loss=loss, mix_weight=0.0)
            if self.mode == "sync":
                key = (cohort_key, replica)
                self._round_weights[key] = (self._round_weights.get(key, 0.0)
                                            + num_samples)
                self._arrived += 1
                self._round_last_arrival = arrival
                # count per client; prune deferred to after the commit
                self._consume(cohort_key, epoch, prune=False)
            else:
                ref = ((cohort_key, epoch, replica)
                       if self.agg_tree == "2level" else trees[replica])
                self._buffer.append((ref, float(num_samples), {
                    "record": record, "pulled_s": pulled_s,
                    "cohort_key": cohort_key, "epoch": epoch}))
            self._applied += 1
        # fire flush points the window has fully covered
        if self.mode == "async" and self._buffer and math.isfinite(bound):
            self._advance_grid(bound)
        if (self.mode == "async" and self._buffer
                and not math.isfinite(bound)
                and self.agg_tree == "2level" and self._mesh is not None):
            # trailing mesh window (every group idle, replay complete):
            # the tail flush needs fold directives, and the drive loop
            # stops the group trainers right after this callback — fire
            # it now, while the mesh is still alive. _finish_run's drain
            # then sees an empty buffer.
            self._drain_async_tail()
        # the range guard matters on the sampled path: after the final
        # commit _expected is 0, and a trailing window callback (peer
        # meshes flush one) would otherwise re-fire an empty commit and
        # record a phantom skipped round
        if self.mode == "sync" and self._round_idx < self.num_rounds \
                and self._arrived == self._expected:
            mail.extend(self._commit_round())
        replay_span.__exit__(None, None, None)
        return mail

    def _commit_round(self) -> List[Mail]:
        r = self._round_idx
        t = self._round_last_arrival
        if not self._round_weights:
            self.agg.commit()                      # empty: carry forward
            self.metrics.record_skipped_round(r, t)
        elif self.agg_tree == "2level":
            # two-level barrier: exact FedAvg coefficients computed here
            # (canonical sequential order), folded into ONE partial per
            # owner group, committed from the merged accumulators —
            # bit-identical to the flat fold for any cohort partition.
            # The exchange runs BEFORE any aggregator mutation: a group
            # death mid-exchange leaves _round_weights/_arrived intact,
            # so the commit re-fires whole after recovery.
            entries = sorted(self._round_weights.items())
            coeffs = sync_coeffs([w for _, w in entries])
            per = [((ck, r, rep), c)
                   for ((ck, rep), _), c in zip(entries, coeffs)]
            acc = self._exchange_partials(per)
            self._round_weights.clear()
            self.fleet.set_global(self.agg.commit_acc(acc, len(per)))
            self.metrics.record_barrier(r, t)
            for cohort_key in self.fleet.cohorts:  # snapshots now consumed
                self._maybe_prune(cohort_key)
        else:
            # gather every update BEFORE the first submit: if a waiter
            # aborts mid-round (group death), the aggregator is still
            # clean and _round_weights/_arrived intact, so the commit
            # re-fires whole after recovery instead of double-counting
            gathered = []
            for (cohort_key, replica), weight in sorted(
                    self._round_weights.items()):
                trees, _ = self._trainer.update_for(cohort_key, r)
                gathered.append((trees[replica], weight))
            self._count_ingress([tree for tree, _ in gathered])
            for tree, weight in gathered:
                self.agg.submit(tree, weight)
            self._round_weights.clear()
            self.fleet.set_global(self.agg.commit())
            self.metrics.record_barrier(r, t)
            for cohort_key in self.fleet.cohorts:  # snapshots now consumed
                self._maybe_prune(cohort_key)
        self._arrived = 0
        self._round_idx = r + 1
        self._expected = self._round_expected(r + 1)
        mail = ([Mail(dst_shard=s, time=t, kind=EventKind.ROUND_START,
                      key="", payload={"round_idx": r + 1})
                 for s in range(self.num_shards)]
                if r + 1 < self.num_rounds else [])
        if self._mesh is not None:
            # mesh path: the restart is control mail to the (quiescing)
            # group processes, not engine mail — sync-mode multi-host.
            # The mail is logged FIRST: if the restart dies mid-send, a
            # rebuilt mesh replays this round's kickoff from the log.
            if mail:
                self._restart_log.append(mail)
                self._mesh.restart(mail)
            return []
        return mail

    # -- entry point -----------------------------------------------------

    def _peer_on_chunk(self):
        """Glue for the peer-driven executor: buffer record shipments and
        forward everything strictly below the advancing safe frontier to
        the ordinary window replay — same code path, same replay order,
        bit-identical results."""
        pend_contribs: List[tuple] = []
        pend_starts: List[tuple] = []
        pend_migs: List[tuple] = []

        def on_chunk(frontier, chunks):
            # repro-lint: allow[deterministic-iteration] buffered records
            # are re-sorted by _on_window's (t, priority, key) replay
            # merge before any of them can touch ordered state
            for recs in chunks.values():
                pend_contribs.extend(recs["contribs"])
                pend_starts.extend(recs["epoch_starts"])
                pend_migs.extend(recs["migrations"])
            if frontier is None:
                return
            take_c = [c for c in pend_contribs if c[0] < frontier]
            take_s = [s for s in pend_starts if s[0] < frontier]
            pend_contribs[:] = [c for c in pend_contribs
                                if c[0] >= frontier]
            pend_starts[:] = [s for s in pend_starts if s[0] >= frontier]
            migs, pend_migs[:] = list(pend_migs), []
            self._on_window(frontier, {0: {
                "contribs": take_c, "epoch_starts": take_s,
                "migrations": migs}})
        return on_chunk

    def _drain_async_tail(self) -> None:
        """Flush any buffered async updates past the last grid point."""
        if self.mode == "async" and self._buffer:
            self._grid_k += 1
            self._fire_flush(self._grid_k * self._flush_dt)

    def _build_result(self, stats: Dict[str, Any]) -> FleetResult:
        """Fold merged engine stats + accumulated metrics into the
        FleetResult (shared by every executor path)."""
        stats["agg"] = {
            "tree": self.agg_tree,
            "ingress_bytes": self._ingress_bytes,
            "root_edge": self._root_edge,
            "root_places": self._root_log,
            "root_moves": self._root_moves,
            "root_move_bytes": self._root_move_bytes,
        }
        by_edge = {e["edge_id"]: e for e in stats.pop("edges")}
        return FleetResult(
            mode=self.mode,
            rounds=self.metrics.build_rounds(),
            migration_summary=self.metrics.migration_summary(),
            engine_stats=stats,
            edge_stats=[by_edge[eid] for eid in self.edge_order],
            final_params=self.agg.params,
            metrics=self.metrics)

    def _round0_mail(self) -> List[Mail]:
        return [Mail(dst_shard=s, time=0.0, kind=EventKind.ROUND_START,
                     key="", payload={"round_idx": 0})
                for s in range(self.num_shards)]

    def _attach_proxy(self, mesh: _MeshEngineBase,
                      cohort_owner: Dict[Tuple, int]) -> TrainerProxy:
        """Swap the inline trainer for the control-mail proxy and wire
        the mesh's reader threads to it (updates routed around the
        replay queue; group deaths poison blocked waiters)."""
        proxy = TrainerProxy(
            mesh.control_send, cohort_owner,
            lr_of=self.fleet.lr_schedule,
            params_of=lambda: self.agg.params,
            version_of=lambda: self.agg.version,
            retain=self.agg_tree == "2level")
        self._trainer = proxy
        self._mesh = mesh
        mesh.on_update = proxy.on_update
        mesh.on_partial = proxy.on_partial
        mesh.on_abort = proxy.abort
        return proxy

    def _mesh_catch_up(self) -> bool:
        """Recovery catch-up hook (``_drive_mesh``'s ``on_idle``,
        ARCHITECTURE §3.7): a rebuilt mesh that idles at a generation
        behind the committed-round log gets the next round's kickoff
        mail re-injected from the log instead of being stopped. On a
        never-failed run the log length always equals the generation at
        every idle (each commit appends immediately before its restart),
        so the hook is inert."""
        mesh = self._mesh
        if mesh is None:
            return False
        if mesh.state.gen < len(self._restart_log):
            mesh.restart(self._restart_log[mesh.state.gen])
            return True
        return False

    def _collect_obs(self, mesh_obs: Optional[Dict[int, List[dict]]]
                     ) -> List[Dict[str, Any]]:
        """Every telemetry snapshot of the run, ordered by rank with the
        coordinator's own (local) drain last."""
        snaps: List[Dict[str, Any]] = []
        if mesh_obs:
            for r in sorted(mesh_obs):
                snaps.extend(mesh_obs[r])
        if obs.is_enabled():
            snap = obs.snapshot()
            if snap is not None:
                snaps.append(snap)
        return snaps

    def _obs_report(self, mesh_obs: Optional[Dict[int, List[dict]]]
                    ) -> Optional[Dict[str, Any]]:
        """Merge snapshots into the summary section, writing the Chrome
        trace file alongside when a path is configured."""
        snaps = self._collect_obs(mesh_obs)
        if not snaps:
            return None
        report = obs_trace.summarize(snaps)
        if self.trace_path:
            obs_trace.write_chrome_trace(self.trace_path, snaps)
            report["trace_path"] = self.trace_path
        return report

    def _finish_run(self, engine: Any, wall0: float) -> FleetResult:
        """Shared tail of every executor path: drain the async flush
        buffer, stamp uniform wall accounting (windows + replay + flush
        drain — engine construction is deliberately excluded, so mesh
        bring-up cost never deflates the events/sec comparison), and
        fold the result."""
        self._drain_async_tail()
        stats = engine.stats()
        stats["wall_s"] = time.perf_counter() - wall0
        stats["events_per_sec"] = (stats["events_processed"]
                                   / stats["wall_s"]
                                   if stats["wall_s"] > 0 else 0.0)
        if self._recovery is not None:       # mesh paths only
            stats["recoveries"] = self._recovery["recoveries"]
            stats["reassigned_shards"] = self._recovery["reassigned_shards"]
            stats["recovery_wall_s"] = self._recovery["recovery_wall_s"]
        result = self._build_result(stats)
        state = getattr(engine, "state", None)
        result.obs = self._obs_report(getattr(state, "obs", None))
        return result

    def run(self, rounds: int) -> FleetResult:
        if self.telemetry:
            obs.enable(rank=obs.COORDINATOR_RANK,
                       process_name="coordinator")
        try:
            return self._run(rounds)
        finally:
            if self.telemetry:
                obs.disable()

    def _run(self, rounds: int) -> FleetResult:
        self.num_rounds = rounds
        self._prepare_sampling(rounds)
        self._expected = self._round_expected(0)
        self._flush_dt = (self.flush_interval_s
                          if self.flush_interval_s is not None
                          else self._min_batch_time())
        shards = self._build_shards(rounds)
        if self.mode == "async":
            for s in shards:
                s.bootstrap_async()
        if self.workers is None and self.hosts is None:
            # serial reference path: inline replay, inline training
            self._trainer = LocalTrainer(self.fleet)
            self._mesh = None
            if self.agg_tree == "2level":
                # every shard is its own "group": the exact fold is
                # partition-invariant, so the serial reference commits
                # the same bits as any mesh grouping
                self._owner_of_shard = {s: s
                                        for s in range(self.num_shards)}
                self._cohort_owner = self._cohort_owners(
                    self._owner_of_shard)
            lookahead = self._lookahead() if self.num_shards > 1 else None
            self.coordinator = ShardedEngine(
                shards, lookahead=lookahead,
                executor=SerialExecutor(shards))
            if self.mode == "sync":
                for m in self._round0_mail():
                    self.coordinator.post(m)
            wall0 = time.perf_counter()
            try:
                self.coordinator.run(self._on_window)
                return self._finish_run(self.coordinator, wall0)
            finally:
                self.coordinator.close()
        # group mesh (pipes or sockets), sync or async: shard-group
        # processes own both the timing engines AND the cohort training;
        # this coordinator replays records, aggregates, and steers the
        # mesh over the control channel. With recovery enabled, a
        # GroupFailure (dead / stalled / unreachable group) rebuilds the
        # mesh over one fewer group, re-assigns shards and cohorts with
        # the reassign/rehello handshake, re-issues outstanding training
        # from the last round broadcast base, and replays from the last
        # committed frontier — ARCHITECTURE §3.7.
        groups0 = max(1, min(self.workers or self.hosts, self.num_shards))
        self._recovery = {"recoveries": 0, "reassigned_shards": 0,
                          "recovery_wall_s": 0.0}
        attempt = 0
        prev_owner: Dict[int, int] = {}
        wall0 = time.perf_counter()
        while True:
            rec0 = time.perf_counter()
            span = (obs.span("coord.recovery", attempt=attempt)
                    if attempt else None)
            if span is not None:
                span.__enter__()
            groups = max(1, groups0 - attempt)
            if attempt:
                # shard timing engines are pure functions of the config;
                # a fresh build replays the same history bit-for-bit
                shards = self._build_shards(rounds)
                if self.mode == "async":
                    for s in shards:
                        s.bootstrap_async()
            owner_of_shard = {s.shard_id: s.shard_id % groups
                              for s in shards}
            cohort_owner = self._cohort_owners(owner_of_shard)
            self._owner_of_shard = owner_of_shard
            self._cohort_owner = cohort_owner
            blobs = self._trainer_blobs(cohort_owner)
            kw: Dict[str, Any] = dict(
                lookahead=self._lookahead(), trainer_blobs=blobs,
                telemetry=self.telemetry, fault_plan=self.fault_plan,
                attempt=attempt,
                barrier_timeout_s=self.barrier_timeout_s,
                control_timeout_s=self.control_timeout_s)
            engine: Any = None
            try:
                if self.hosts is not None:
                    engine = HostShardedEngine(shards, hosts=groups, **kw)
                else:
                    engine = PeerShardedEngine(shards, groups=groups, **kw)
                self.coordinator = engine
                if attempt == 0:
                    self._attach_proxy(engine, cohort_owner)
                else:
                    # keep the proxy — its update store and request log
                    # ARE the recovery state; re-arm it on the new mesh
                    proxy = self._trainer
                    self._mesh = engine
                    engine.on_update = proxy.on_update
                    engine.on_partial = proxy.on_partial
                    engine.on_abort = proxy.abort
                    reassigned = sum(
                        1 for sid in sorted(owner_of_shard)
                        if prev_owner.get(sid) != owner_of_shard[sid])
                    self._recovery["reassigned_shards"] += reassigned
                    obs.count("coord.reassigned_shards", reassigned)
                    for g in range(engine.num_groups):
                        engine.control_send(
                            g, {"type": "reassign",
                                "owner": owner_of_shard,
                                "epoch": attempt})
                    proxy.reset_for_recovery(
                        engine.control_send, cohort_owner,
                        drop_stored=self.agg_tree == "2level")
                engine.on_idle = self._mesh_catch_up
                if self.fault_plan is not None:
                    for f in self.fault_plan.for_coordinator(attempt):
                        engine.drop_ctrl(f.group % engine.num_groups)
                prev_owner = owner_of_shard
                self._skip = self._applied
                if span is not None:
                    span.__exit__(None, None, None)
                    span = None
                    self._recovery["recovery_wall_s"] += (
                        time.perf_counter() - rec0)
                if self.mode == "sync":
                    if attempt == 0:
                        self._restart_log.append(self._round0_mail())
                    engine.restart(self._restart_log[0])
                engine.run(self._peer_on_chunk())
                return self._finish_run(engine, wall0)
            except (GroupFailure, TrainerAborted, OSError, EOFError):
                if engine is not None:
                    # silence the dead mesh BEFORE closing it: its
                    # reader threads can still fire a late abort that
                    # would poison the re-armed proxy
                    engine.on_abort = None
                    engine.on_update = None
                    engine.on_partial = None
                    engine.on_idle = None
                    engine.close()
                    engine = None
                if not self.recovery or attempt >= self.max_recoveries:
                    raise
                self._recovery["recoveries"] += 1
                obs.count("coord.recoveries")
                attempt += 1
            finally:
                if span is not None:
                    span.__exit__(None, None, None)
                if engine is not None:
                    engine.close()
                self._mesh = None

    def run_multihost(self, rounds: int, *, rank: int,
                      listen: Tuple[str, int],
                      addresses: Dict[int, Tuple[str, int]]
                      ) -> Optional[FleetResult]:
        """Run this process's slice of a simulation spread over separate
        machines (``examples/fleet_sim_multihost.py``). Every rank must
        construct an *identical* FleetSimulator (same fleet, edges, seed,
        spec) and call this with the same ``addresses`` directory
        ``{rank: (host, port)}``; ``listen`` is the (host, port) this
        rank binds. Rank 0 is the coordinator — it replays the numerics,
        steers the mesh over per-rank ``ctrl`` streams (sync round
        restarts, model broadcasts, train directives), and returns the
        ``FleetResult`` — and every rank, 0 included, runs one
        shard-group host loop plus the cohort trainer for the cohorts it
        owns. The window barrier, cross-shard mail, record shipments,
        control mail, and update snapshots all ride TCP frames
        (docs/ARCHITECTURE.md); results are bit-identical to a
        single-process ``SerialExecutor`` run, sync or async."""
        if self.measure_pack:
            raise ValueError("run_multihost requires measure_pack=False")
        if self.telemetry:
            # every rank is a host; rank 0 is additionally the
            # coordinator (its coordinator-side spans ship with — and
            # under the lane of — its own host loop)
            obs.enable(rank=rank, process_name=f"host {rank}")
        hosts = len(addresses)
        if sorted(addresses) != list(range(hosts)):
            raise ValueError(
                f"address directory must map ranks 0..{hosts - 1} "
                f"exactly, got {sorted(addresses)} — a gapped directory "
                "would orphan shards and drop their mail")
        if rank not in addresses:
            raise ValueError(f"rank {rank} not in the address directory")
        self.num_rounds = rounds
        self._prepare_sampling(rounds)
        self._expected = self._round_expected(0)
        self._flush_dt = (self.flush_interval_s
                          if self.flush_interval_s is not None
                          else self._min_batch_time())
        shards = self._build_shards(rounds)
        owner = {s.shard_id: s.shard_id % hosts for s in shards}
        group = [s for s in shards if owner[s.shard_id] == rank]
        if self.mode == "async":
            for s in group:
                s.bootstrap_async()
        lookahead = self._lookahead()
        cohort_owner = self._cohort_owners(owner)
        self._owner_of_shard = owner
        self._cohort_owner = cohort_owner
        specs = self.fleet.cohort_specs()
        barrier_s = self.barrier_timeout_s or _BARRIER_TIMEOUT_S
        control_s = self.control_timeout_s or _BARRIER_TIMEOUT_S
        mailbox = SocketMailbox(rank, host=listen[0], port=listen[1],
                                backlog=hosts + 4,
                                barrier_timeout_s=barrier_s)
        sink = SocketRecordSink(addresses[0], rank)
        mailbox.connect(addresses)
        # this rank's trainer: the cohorts it owns, rebuilt from the
        # locally-constructed fleet (nothing JAX-flavored on the wire)
        trainer = GroupTrainer(
            [specs[k] for k in sorted(cohort_owner)
             if cohort_owner[k] == rank], sink, group_id=rank)
        barrier_q = _dispatch_control(mailbox.control, trainer)
        ctrl: Optional[Any] = None
        wall0 = time.perf_counter()
        try:
            if rank != 0:
                run_host_windows(group, mailbox, lookahead, sink, owner,
                                 control=barrier_q, trainer=trainer,
                                 control_timeout_s=control_s)
                return None
            # rank 0: drive our own shard group in a thread (it is
            # JAX-free; the trainer runs on its own thread either way)
            # while this thread drains records and replays the numerics
            # — the same split HostShardedEngine gets from its children
            def host_loop():
                try:
                    run_host_windows(group, mailbox, lookahead, sink,
                                     owner, control=barrier_q,
                                     trainer=trainer,
                                     control_timeout_s=control_s)
                except BaseException:
                    import traceback
                    try:
                        sink.err(traceback.format_exc())
                    except OSError:
                        pass
            th = threading.Thread(target=host_loop, daemon=True)
            th.start()
            ctrl = MultihostControl(addresses, owner)
            proxy = self._attach_proxy(ctrl, cohort_owner)
            mailbox.on_update = proxy.on_update
            mailbox.on_partial = proxy.on_partial
            mailbox.on_abort = proxy.abort
            if self.mode == "sync":
                ctrl.restart(self._round0_mail())
            finals, trainers = _drive_mesh(
                lambda t: mailbox.records.get(timeout=t), ctrl.state,
                self._peer_on_chunk(), ctrl.stop_all,
                timeout_s=control_s)
            th.join()
            self._drain_async_tail()
            stats = merge_host_finals(
                finals, wall_s=time.perf_counter() - wall0,
                num_shards=len(shards), num_hosts=hosts,
                trainers=trainers)
            result = self._build_result(stats)
            result.obs = self._obs_report(ctrl.state.obs)
            return result
        finally:
            if self.telemetry:
                obs.disable()
            # unblock this process's control dispatcher (and through it
            # the trainer thread) even on an abort path — run_multihost
            # is a library call in a long-lived process, and a retry
            # after a failed run must not accumulate blocked threads.
            # Redundant after a clean stop: the dispatcher has already
            # exited and nothing consumes the extra message.
            mailbox.control.put({"type": "stop"})
            mailbox.close()
            sink.close()
            if ctrl is not None:
                ctrl.close()
            self._mesh = None
