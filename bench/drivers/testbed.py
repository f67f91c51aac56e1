"""Driver for the paper testbed through ``FedFlyScheduler``.

The configuration file gives the model, optimizer and deployment; the
traffic file gives the data partition, who moves when, and the
migration codec. One ``Cell`` is built per run:

``setup``   makes the data from the seed, builds the scheduler, and
            trains round 0 as warm-up. Round 0 compiles every program
            the window uses (the split step, the fold, the codec) and
            feeds the training check and the fold check: the first three
            steps of every client go through the scheduler's own step
            and feed, and round 0's fold is the window's program at the
            window's shapes. Both are copied to the host and reduced to
            the numbers compared there.
``window``  runs whole rounds (``run_round``) until ``seconds`` have
            passed; each round ends in a host read of the folded model.
            Its migrations are sampled from the host trees the moves
            themselves fetch and restore, so the window copies nothing
            more.
``numbers`` after the window: the training check, round 0's fold
            numbers, and the migration sample against the
            codec's bound, with the packed quantize's codes and scales
            against the numpy reference.

What depends on the model are hooks, VGG-5's by default:

``check_config()``   raises where the configuration is not the model the
                     program builds.
``build_model()``    the program's model.
``build_optimizer()`` the program's optimizer.
``build_batchers()`` one batcher per client, from the seed and the
                     traffic's shares: ``batch_at(epoch, b)`` gives a
                     batch (a dict of arrays), ``num_batches`` their
                     count an epoch, ``len(ds)`` the client's samples.
``merge(dev, srv)``, ``first_grad(opt_state)``  as ``cellbase`` says.

Another configuration's driver is a file of its own that loads this one
(``harness.driver("testbed")``), subclasses its ``Cell`` and overrides
the hooks that differ; the scheduler, the instrumentation and the window
stay here.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, List, Optional

import numpy as np

import data
from cellbase import CellBase

MAX_ROUNDS = 4096          # mobility trace length; a window uses far fewer


def _tree_leaves(tree) -> List[Any]:
    import jax
    return jax.tree.leaves(tree)


class Cell(CellBase):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.stalls: List[float] = []
        self._client = None
        self._move_t0: Optional[float] = None
        self._quant = None
        self._sent = None
        self._unpatch = lambda: None
        self.round = 0

    # -- build ------------------------------------------------------------

    def check_config(self) -> None:
        from repro.models.vgg import VGG5_LAYERS
        want = [tuple(l) for l in self.config["layers"]]
        have = [(k, *s) for k, s in VGG5_LAYERS]
        if want != have:
            raise ValueError(f"configuration layers {want} are not the "
                             f"program's VGG-5 {have}")

    def build_model(self):
        from repro.models.vgg import VGG5
        return VGG5()

    def build_optimizer(self):
        from repro.optim.optimizers import sgd
        return sgd(momentum=self.config["momentum"])

    def build_batchers(self) -> List[Any]:
        from repro.data.datasets import ImageDataset
        from repro.data.loader import Batcher
        cfg, tr = self.config, self.traffic
        x, y = data.images(tr["samples"], cfg["image"], self.seed)
        parts = data.split(tr["samples"], tr["fractions"], self.seed)
        return [Batcher(ImageDataset(x[i], y[i]), cfg["batch_size"],
                        seed=self.seed) for i in parts]

    def merge(self, dev, srv):
        return list(dev) + list(srv)

    def first_grad(self, opt_state):
        return opt_state["mu"]

    def setup(self) -> None:
        from repro.core.mobility import MobilityTrace, MoveEvent
        from repro.core.scheduler import FedFlyScheduler
        from repro.optim.schedules import constant
        from repro.runtime.cluster import (WIFI_75MBPS, make_testbed_devices,
                                           make_testbed_edges)
        cfg, tr = self.config, self.traffic
        self.check_config()
        devices = make_testbed_devices(self.build_batchers(),
                                       tuple(cfg["edges"]))
        if [d.client_id for d in devices] != cfg["clients"]:
            raise ValueError("configuration clients differ from the testbed")
        self.model = self.build_model()
        sched = FedFlyScheduler(
            self.model, self.build_optimizer(), devices,
            make_testbed_edges(), split_point=cfg["split_point"],
            lr_schedule=constant(cfg["lr"]), link=WIFI_75MBPS,
            migration_codec=tr["codec"], seed=self.seed)
        sched.initialize()
        self.sched = sched
        home = {d.client_id: d.edge_id for d in devices}
        moves = tr.get("moves")
        events = [] if not moves else data.handoffs(
            cfg["clients"], cfg["edges"], home,
            cfg["clients"] if moves["clients"] == "all" else moves["clients"],
            moves["fraction"], MAX_ROUNDS, self.seed)
        self.trace = MobilityTrace([MoveEvent(*e) for e in events])
        self.batches_per_round = sum(d.batcher.num_batches
                                     for d in devices)
        self._instrument()
        self.run_rounds(1)                       # warm-up: round 0
        self.capture.on = False
        if not self.capture.complete():
            raise RuntimeError("warm-up did not capture three steps of "
                               "every client")

    def _instrument(self) -> None:
        sched, spans = self.sched, self.spans
        for dev in sched.devices.values():
            batch_at = dev.batcher.batch_at

            def wrapped(epoch, b, _f=batch_at, _c=dev.client_id):
                self._client = _c
                with spans.span("batch"):
                    return _f(epoch, b)
            dev.batcher.batch_at = wrapped

        step = sched._step
        bs = self.config["batch_size"]

        def step_w(*args):
            self.tick(bs)
            self.capture.before(self._client, args)
            with spans.span("step"):
                outs = step(*args)
            self.capture.record(self._client, outs)
            return outs
        sched._step = step_w

        do_move = sched._do_move

        def move_w(*args, **kw):
            self._move_t0 = time.perf_counter()
            self.capture.close(self._client)
            with spans.span("migrate"):
                return do_move(*args, **kw)
        sched._do_move = move_w

        costs = sched.cost_model.costs

        def costs_w(*args, **kw):
            # called right after the scheduler read the batch's loss back:
            # the first one after a move ends that move's stall
            if self._move_t0 is not None:
                self.stalls.append(time.perf_counter() - self._move_t0)
                self._move_t0 = None
            return costs(*args, **kw)
        sched.cost_model.costs = costs_w

        migrate = sched.migrator.migrate

        def migrate_w(ckpt, src, dst, **kw):
            base = (sched.base_registry.base_for(dst)[0]
                    if sched.base_registry is not None else None)
            self._quant = self._sent = None
            restored, report = migrate(ckpt, src, dst, **kw)
            if self.round > 0:           # the window's, not warm-up's
                self.sample_migration(
                    self._sent, base,
                    {f.name: getattr(restored, f.name)
                     for f in dataclasses.fields(restored)}, self._quant)
            self._sent = None
            return restored, report
        sched.migrator.migrate = migrate_w

        # the host tree the move's own device-to-host fetch made
        from repro.core.checkpoint import EdgeCheckpoint
        to_tree = EdgeCheckpoint.to_tree

        def to_tree_w(ckpt):
            self._sent = to_tree(ckpt)
            return self._sent
        EdgeCheckpoint.to_tree = to_tree_w

        # the packed quantize's inputs and outputs, as the codec ran it
        from repro.kernels.int8_codec import ops as codec_ops
        quantize = codec_ops.quantize_leaves

        def quantize_w(leaves, base_leaves=None, **kw):
            out = quantize(leaves, base_leaves, **kw)
            self._quant = (list(leaves), None if base_leaves is None
                           else list(base_leaves), out[0], out[1])
            return out
        codec_ops.quantize_leaves = quantize_w

        def unpatch():
            codec_ops.quantize_leaves = quantize
            EdgeCheckpoint.to_tree = to_tree
            self._unpatch = lambda: None
        self._unpatch = unpatch

        aggregate = sched._aggregate

        def aggregate_w():
            take = self.fold is None     # round 0's, in warm-up
            if take:
                trees, weights = [], []
                for dev in sched.devices.values():
                    st = sched.edges[dev.edge_id].clients[dev.client_id]
                    trees.append(self.host_leaves((dev.dev_params,
                                                   st.srv_params)))
                    weights.append(dev.num_samples)
            with spans.span("aggregate"):
                aggregate()
            if take:
                self.take_fold(trees, weights,
                               [np.asarray(x) for x in
                                _tree_leaves(sched.global_params)])
        sched._aggregate = aggregate_w

    # -- run --------------------------------------------------------------

    def run_rounds(self, n: int) -> Dict[str, int]:
        import jax
        out = {"batches": 0, "migrations": 0, "failed": 0}
        nb = {c: d.batcher.num_batches for c, d in self.sched.devices.items()}
        for _ in range(n):
            rec = self.sched.run_round(self.round, self.trace)
            jax.block_until_ready(self.sched.global_params)
            self.round += 1
            out["batches"] += self.batches_per_round
            out["migrations"] += len(rec.migrations)
            out["failed"] += sum(nb[c] for c, v in rec.client_losses.items()
                                 if not math.isfinite(v))
            out["failed"] += sum(1 for m in rec.migrations
                                 if not math.isfinite(m.quant_error))
        return out

    def window(self, seconds: float) -> Dict[str, Any]:
        self.stalls = []
        totals = {"batches": 0, "migrations": 0, "failed": 0}
        t0 = time.perf_counter()
        while True:
            for k, v in self.run_rounds(1).items():
                totals[k] += v
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
        totals.update(elapsed_s=elapsed,
                      samples=totals["batches"] * self.config["batch_size"],
                      stalls_s=list(self.stalls))
        return totals

    def release(self) -> None:
        """Drop the program's state before the reference runs, and give
        the program's classes back their own methods; a second call does
        nothing more."""
        self._unpatch()
        self.sched = None
