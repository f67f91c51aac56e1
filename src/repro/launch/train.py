"""Training driver.

Two modes:
  testbed  — the paper's system end-to-end on CPU: simulated devices +
             edge servers, split training, mobility trace, migration
             (FedFly) or restart (SplitFed). Works with VGG-5 (the
             paper's model) or any assigned arch in its reduced variant.
  spmd     — a single-process jit training loop of the full (or reduced)
             model on whatever devices exist, using the same sharding
             rules as the production dry-run. On this CPU container use
             --reduced; the full configs are exercised via
             ``repro.launch.dryrun``.

Examples:
  PYTHONPATH=src python -m repro.launch.train --mode testbed --rounds 5 \\
      --move-client pi3_1 --move-round 2 --move-fraction 0.5
  PYTHONPATH=src python -m repro.launch.train --mode spmd --arch yi-6b \\
      --reduced --steps 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import INPUT_SHAPES
from repro.core.mobility import MobilityTrace, move_at_round
from repro.core.scheduler import FedFlyScheduler
from repro.data.datasets import synthetic_cifar10, synthetic_tokens
from repro.data.loader import Batcher
from repro.data.partition import balanced, by_fraction
from repro.launch import sharding as sh
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compilation_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import build_model, get_config, make_reduced
from repro.obs import log as obs_log
from repro.models.vgg import VGG5
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant
from repro.runtime.cluster import (WIFI_75MBPS, make_testbed_devices,
                                   make_testbed_edges)

log = obs_log.get_logger("launch.train")


def run_testbed(args) -> None:
    train, test = synthetic_cifar10(n_train=args.samples,
                                    n_test=args.samples // 5)
    if args.mobile_fraction > 0:
        rest = (1.0 - args.mobile_fraction) / 3
        parts = by_fraction(train, [args.mobile_fraction, rest, rest, rest])
    else:
        parts = balanced(train, 4)
    batchers = [Batcher(p, args.batch_size) for p in parts]

    if args.arch:
        cfg = make_reduced(get_config(args.arch))
        model = build_model(cfg)
        sp = min(cfg.default_split, cfg.num_layers - 1)
        # token batchers: reuse image batcher shapes via synthetic tokens
        raise SystemExit("testbed mode trains VGG-5 (the paper's model); "
                         "use --mode spmd for the LLM archs")
    model = VGG5()
    sp = args.split_point

    sched = FedFlyScheduler(
        model, sgd(momentum=0.9), make_testbed_devices(batchers),
        make_testbed_edges(), split_point=sp,
        lr_schedule=constant(args.lr), link=WIFI_75MBPS,
        migration_codec=args.codec, seed=args.seed)
    sched.initialize()

    trace = None
    if args.move_client:
        trace = MobilityTrace(move_at_round(
            args.move_client, "edge-A", "edge-B", args.move_round,
            fraction=args.move_fraction))

    def eval_fn(params):
        logits = model.forward(params, test.images[:1024])
        return float((jnp.argmax(logits, -1)
                      == test.labels[:1024]).mean())

    hist = sched.run(args.rounds, trace, mode=args.fl_mode,
                     eval_fn=eval_fn, eval_every=args.eval_every)
    for r in hist.rounds:
        mig = "".join(f" [migrated {m.client_id} {m.src_edge}->{m.dst_edge} "
                      f"{m.nbytes/1e6:.1f}MB {m.sim_total_s:.2f}s]"
                      for m in r.migrations)
        rst = f" [restarted {r.restarted}]" if r.restarted else ""
        log.info("round %3d  sim=%7.2fs  wall=%6.2fs  loss=%.4f%s%s",
                 r.round_idx, r.round_time_sim, r.round_time_wall,
                 np.mean(list(r.client_losses.values())), mig, rst)
        if r.round_idx in hist.eval_acc:
            log.info("          eval acc: %.3f", hist.eval_acc[r.round_idx])
    log.info("total simulated training time: %.1fs  "
             "migration overhead: %.2fs",
             hist.total_time_sim(), sched.migrator.total_overhead_s())


def run_spmd(args) -> None:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    model = build_model(cfg)
    mesh = make_host_mesh()
    shape = INPUT_SHAPES[args.shape]
    B = min(shape.global_batch, args.batch_size)
    S = min(shape.seq_len, args.seq_len)

    params = model.init(jax.random.PRNGKey(args.seed))
    opt = sgd(momentum=0.9)
    opt_state = opt.init(params)
    step = steps_lib.make_train_step(model, opt)
    p_sh = sh.param_shardings(jax.eval_shape(lambda: params), mesh)
    jitted = jax.jit(step, in_shardings=(p_sh, None, None, None),
                     donate_argnums=(0, 1))

    data = synthetic_tokens(B, S, cfg.vocab_size, args.seed)
    batch = {k: jnp.asarray(v) for k, v in data.items()}
    if cfg.vision_prefix:
        batch["vision_embeds"] = jnp.zeros((B, cfg.vision_prefix,
                                            cfg.d_model), jnp.float32)
    if cfg.encoder_layers:
        batch["frames"] = jnp.zeros((B, cfg.encoder_seq, cfg.d_model),
                                    jnp.float32)

    with mesh:
        for i in range(args.steps):
            t0 = time.perf_counter()
            params, opt_state, metrics = jitted(params, opt_state, batch,
                                                jnp.float32(args.lr))
            loss = float(metrics["loss"])
            log.info("step %4d  loss=%.4f  (%.2fs)",
                     i, loss, time.perf_counter() - t0)
            assert np.isfinite(loss), "loss diverged"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("testbed", "spmd"), default="testbed")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--samples", type=int, default=4000)
    ap.add_argument("--batch-size", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--split-point", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fl-mode", choices=("fedfly", "splitfed"),
                    default="fedfly")
    ap.add_argument("--codec", choices=("raw", "int8"), default="raw")
    ap.add_argument("--mobile-fraction", type=float, default=0.25)
    ap.add_argument("--move-client", default=None)
    ap.add_argument("--move-round", type=int, default=2)
    ap.add_argument("--move-fraction", type=float, default=0.5)
    ap.add_argument("--eval-every", type=int, default=0)
    obs_log.add_verbosity_flags(ap)
    args = ap.parse_args()
    obs_log.setup(verbosity=obs_log.verbosity_from_args(args))
    enable_compilation_cache()
    if args.mode == "testbed":
        run_testbed(args)
    else:
        if not args.arch:
            raise SystemExit("--mode spmd requires --arch")
        run_spmd(args)


if __name__ == "__main__":
    main()
