"""Synchronous FL round scheduler with mid-round migration.

Implements the full FedFly protocol of Fig. 1/Fig. 2:

  Step 1    central server broadcasts global params to edges/devices
  Step 2-3  each device trains one local epoch through its edge server
            (split forward/backward, ``repro.core.split``)
  Step 4-5  central server FedAvg-aggregates the merged full models
  Step 6-9  if a device moves mid-epoch: checkpoint → transfer → resume
            at the destination edge server (mode="fedfly"), or restart
            the local epoch from batch 0 (mode="splitfed", the paper's
            baseline).

The scheduler keeps two clocks per round and per client:
  sim_s   — the simulated testbed clock (hardware profiles + link model),
            which reproduces the paper's Fig. 3 numbers;
  wall_s  — real CPU wall-clock of the executed JAX steps.

All devices train logically in parallel; the round time is the max over
clients (synchronous FL). Training is *deterministic* given seeds, so
FedFly-vs-SplitFed comparisons are exact.

A batch's loss stays on the device until the protocol needs it as a
float: before a move (the checkpoint carries it), on the first batch
trained after a move (its arrival ends the migration stall), and at
the epoch's end (the round's record). Between those points the host
prepares and dispatches the next batch while the device runs the last.

With telemetry on (``repro.obs``), every batch records ``sched.put``,
``sched.dispatch`` and ``sched.cost`` spans, each of the three reads a
``sched.readback`` span, and every move ``sched.move`` with
``sched.restore`` inside (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fedavg as fedavg_lib
from repro.core import split as split_lib
from repro.core.checkpoint import EdgeCheckpoint
from repro.core.migration import MigrationExecutor, MigrationReport
from repro.core.mobility import MobilityTrace
from repro.obs import telemetry as obs
from repro.optim.optimizers import Optimizer
from repro.runtime.checkpoint_manager import BaseVersionRegistry
from repro.runtime.cluster import (Device, EdgeServer, ClientServerState,
                                   StageCostModel, batch_time_s)
from repro.runtime.transport import LinkModel

Params = Any


@dataclass
class RoundRecord:
    round_idx: int
    client_times_sim: Dict[str, float]
    client_times_wall: Dict[str, float]
    client_losses: Dict[str, float]
    migrations: List[MigrationReport] = field(default_factory=list)
    restarted: List[str] = field(default_factory=list)

    @property
    def round_time_sim(self) -> float:
        return max(self.client_times_sim.values())

    @property
    def round_time_wall(self) -> float:
        return max(self.client_times_wall.values())


@dataclass
class History:
    rounds: List[RoundRecord] = field(default_factory=list)
    eval_acc: Dict[int, float] = field(default_factory=dict)

    def total_time_sim(self) -> float:
        return sum(r.round_time_sim for r in self.rounds)

    def client_round_times(self, client_id: str) -> List[float]:
        return [r.client_times_sim[client_id] for r in self.rounds]


class FedFlyScheduler:
    """Drives FL rounds over a simulated cluster of devices + edges."""

    def __init__(self, model, optimizer: Optimizer, devices: List[Device],
                 edges: List[EdgeServer], *, split_point: int,
                 lr_schedule: Callable[[int], float],
                 link: LinkModel = LinkModel(),
                 migration_codec: str = "raw",
                 migration_route: str = "direct",
                 seed: int = 0):
        self.model = model
        self.optimizer = optimizer
        self.devices = {d.client_id: d for d in devices}
        self.edges = {e.edge_id: e for e in edges}
        self.sp = split_point
        self.lr_schedule = lr_schedule
        self.link = link
        # delta codec: every edge receives the round broadcast, so each
        # round's server-stage partition is a base version every edge
        # holds — migrations ship only the drift since round start
        self.base_registry = (BaseVersionRegistry()
                              if migration_codec == "delta" else None)
        self._base_counter = 0
        self.migrator = MigrationExecutor(link=link, codec=migration_codec,
                                          base_registry=self.base_registry)
        self.migration_route = migration_route
        self.cost_model = StageCostModel()
        self.seed = seed
        self.global_params: Params = None
        self._step = None   # jitted split train step

    # -- setup ----------------------------------------------------------

    def initialize(self, key=None):
        key = key if key is not None else jax.random.PRNGKey(self.seed)
        self.global_params = self.model.init(key)
        self._broadcast()
        self._build_step()

    def _broadcast(self):
        """Step 1 / Step 6 of Fig. 1: push global params to all stages."""
        for dev in self.devices.values():
            d, s = split_lib.partition_params(self.model, self.global_params,
                                              self.sp)
            dev.dev_params = d
            dev.dev_opt = self.optimizer.init(d)
            edge = self.edges[dev.edge_id]
            edge.clients[dev.client_id] = ClientServerState(
                srv_params=s, srv_opt=self.optimizer.init(s))
        self._publish_base()

    def _publish_base(self):
        """Register this broadcast's server-stage partition as a synced
        base version on every edge (they all just received it): the
        delta migration codec encodes residuals against it."""
        if self.base_registry is None:
            return
        _, s = split_lib.partition_params(self.model, self.global_params,
                                          self.sp)
        version = f"v{self._base_counter}"
        self._base_counter += 1
        self.base_registry.publish(
            version, {"server_params": jax.tree.map(np.asarray, s)})
        self.base_registry.mark_all_synced(self.edges.keys(), version)

    def _build_step(self):
        model, sp, opt = self.model, self.sp, self.optimizer

        def step(dev_p, srv_p, dev_opt, srv_opt, batch, lr):
            loss, g_dev, g_srv = split_lib.split_value_and_grad(
                model, dev_p, srv_p, batch, sp)
            new_dev, dev_opt = opt.update(g_dev, dev_opt, dev_p, lr)
            new_srv, srv_opt = opt.update(g_srv, srv_opt, srv_p, lr)
            return new_dev, new_srv, dev_opt, srv_opt, loss, g_srv

        self._step = jax.jit(step)

    # -- one client's local epoch (with migration) -----------------------

    def _train_client_round(self, round_idx: int, client_id: str,
                            trace: Optional[MobilityTrace], mode: str,
                            record: RoundRecord):
        dev = self.devices[client_id]
        edge = self.edges[dev.edge_id]
        state = edge.clients[client_id]
        batcher = dev.batcher
        nb = batcher.num_batches
        lr = jnp.float32(self.lr_schedule(round_idx))

        move = trace.move_for(round_idx, client_id) if trace else None
        move_at = None
        if move is not None:
            # clamp inside the epoch: fraction < 1 must still move even
            # when round(f*nb) lands on nb (e.g. 90% of 4 batches)
            move_at = min(int(round(move.fraction * nb)), nb - 1)

        t_sim = 0.0
        t_wall0 = time.perf_counter()
        moved = False
        resumed = False     # the next batch is the first after the move
        b = state.batch_idx
        loss_val = state.last_loss

        while b < nb:
            if move is not None and not moved and b == move_at:
                with obs.span("sched.move", client=client_id):
                    t_sim += self._do_move(round_idx, dev, move, mode,
                                           record, b, loss_val)
                moved = resumed = True
                edge = self.edges[dev.edge_id]
                state = edge.clients[client_id]
                if mode == "splitfed":
                    b = 0           # restart the local epoch at destination
                continue

            host = batcher.batch_at(state.epoch, b)
            with obs.span("sched.put"):
                batch = self._augment_batch(
                    {k: jnp.asarray(v) for k, v in host.items()})
            with obs.span("sched.dispatch"):
                (dev.dev_params, state.srv_params, dev.dev_opt,
                 state.srv_opt, loss, g_srv) = self._step(
                    dev.dev_params, state.srv_params, dev.dev_opt,
                    state.srv_opt, batch, lr)
            state.last_grads = g_srv
            state.batch_idx = b + 1
            # the protocol's sync points: the move next checkpoints this
            # loss; the first batch after a move ends the stall only once
            # its loss is on the host; the last batch ends the epoch
            if (resumed or b == nb - 1
                    or (not moved and b + 1 == move_at)):
                with obs.span("sched.readback"):
                    loss_val = float(loss)
                state.last_loss = loss_val
                resumed = False

            with obs.span("sched.cost"):
                dflops, sflops, sbytes = self.cost_model.costs(
                    self.model, dev.dev_params, state.srv_params, batch,
                    self.sp)
                t_sim += batch_time_s(dev.profile, edge.profile, self.link,
                                      dflops, sflops, sbytes)
            b += 1

        state.epoch += 1
        state.batch_idx = 0
        record.client_times_sim[client_id] = t_sim
        record.client_times_wall[client_id] = time.perf_counter() - t_wall0
        record.client_losses[client_id] = loss_val

    def _augment_batch(self, batch):
        """Attach stub modality inputs for vlm/audio archs."""
        cfg = getattr(self.model, "cfg", None)
        if cfg is None:
            return batch
        B = next(iter(batch.values())).shape[0]
        if getattr(cfg, "vision_prefix", 0) and "vision_embeds" not in batch:
            batch["vision_embeds"] = jnp.zeros(
                (B, cfg.vision_prefix, cfg.d_model), jnp.float32)
        if getattr(cfg, "encoder_layers", 0) and "frames" not in batch:
            batch["frames"] = jnp.zeros(
                (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
        return batch

    # -- the migration event (Fig. 2 steps 6-9) ---------------------------

    def _do_move(self, round_idx: int, dev: Device, move, mode: str,
                 record: RoundRecord, batch_idx: int,
                 loss_val: float) -> float:
        """Returns the simulated-clock cost of the move."""
        src = self.edges[move.src_edge]
        dst = self.edges[move.dst_edge]
        state = src.clients.pop(dev.client_id)
        dev.edge_id = dst.edge_id

        if mode == "fedfly":
            ckpt = EdgeCheckpoint(
                client_id=dev.client_id, round_idx=round_idx,
                epoch=state.epoch, batch_idx=batch_idx,
                split_point=self.sp, server_params=state.srv_params,
                optimizer_state=state.srv_opt, last_grads=state.last_grads,
                loss=loss_val, rng_seed=self.seed)
            restored, report = self.migrator.migrate(
                ckpt, move.src_edge, move.dst_edge,
                route=self.migration_route)
            record.migrations.append(report)
            with obs.span("sched.restore"):
                srv_params = jax.tree.map(jnp.asarray,
                                          restored.server_params)
                srv_opt = jax.tree.map(jnp.asarray, restored.optimizer_state)
            dst.clients[dev.client_id] = ClientServerState(
                srv_params=srv_params, srv_opt=srv_opt,
                epoch=restored.epoch, batch_idx=restored.batch_idx,
                last_loss=restored.loss)
            return report.sim_total_s

        # SplitFed baseline: no migration; the destination edge pulls the
        # round-start global model from the central server and the device
        # restarts its local epoch (paper §V-B: "training is restarted").
        record.restarted.append(dev.client_id)
        d0, s0 = split_lib.partition_params(self.model, self.global_params,
                                            self.sp)
        dev.dev_params, dev.dev_opt = d0, self.optimizer.init(d0)
        dst.clients[dev.client_id] = ClientServerState(
            srv_params=s0, srv_opt=self.optimizer.init(s0),
            epoch=state.epoch, batch_idx=0)
        # time cost: fetching params from central server over the edge link
        nbytes = sum(int(np.prod(np.shape(x))) * np.asarray(x).dtype.itemsize
                     for x in jax.tree.leaves(self.global_params))
        return self.link.transfer_time(nbytes)

    # -- rounds -----------------------------------------------------------

    def run_round(self, round_idx: int, trace: Optional[MobilityTrace],
                  mode: str = "fedfly") -> RoundRecord:
        record = RoundRecord(round_idx, {}, {}, {})
        for client_id in self.devices:
            self._train_client_round(round_idx, client_id, trace, mode,
                                     record)
        self._aggregate()
        return record

    def _aggregate(self):
        """Steps 4-5: FedAvg over merged full models, weighted by client
        dataset size, then re-broadcast (Step 6)."""
        trees, weights = [], []
        for dev in self.devices.values():
            state = self.edges[dev.edge_id].clients[dev.client_id]
            trees.append(split_lib.merge_params(self.model, dev.dev_params,
                                                state.srv_params))
            weights.append(dev.num_samples)
        self.global_params = fedavg_lib.fedavg(trees, weights)
        self._rebroadcast_params_only()

    def _rebroadcast_params_only(self):
        """Push the new global model; optimizer state persists per client
        (matching the reference FedFly implementation)."""
        for dev in self.devices.values():
            d, s = split_lib.partition_params(self.model, self.global_params,
                                              self.sp)
            dev.dev_params = d
            state = self.edges[dev.edge_id].clients[dev.client_id]
            state.srv_params = s
        self._publish_base()

    def run(self, num_rounds: int, trace: Optional[MobilityTrace] = None,
            mode: str = "fedfly",
            eval_fn: Optional[Callable[[Params], float]] = None,
            eval_every: int = 0) -> History:
        if self.global_params is None:
            self.initialize()
        hist = History()
        for r in range(num_rounds):
            hist.rounds.append(self.run_round(r, trace, mode))
            if eval_fn is not None and eval_every and (r + 1) % eval_every == 0:
                hist.eval_acc[r] = float(eval_fn(self.global_params))
        return hist
