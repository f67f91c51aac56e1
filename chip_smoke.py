#!/usr/bin/env python3
"""Smoke test of FedFly's main path on one TPU chip.

    python chip_smoke.py [--seed N]

One process drives the system through its normal entry points with
VGG-5 at the paper's widths (split point SP2), on synthetic CIFAR-10
made from ``--seed``. Phases, each of which raises on failure:

  device   the first JAX device must be a TPU; there is no CPU fallback.
  testbed  ``FedFlyScheduler``: batch 100, 4 clients on 2 edges, 2 rounds,
           pi3_1 moves edge-A -> edge-B at 50 % of round 1, once per
           migration codec (raw, int8, delta). Losses are finite; the
           raw move resumes bit-identically to a run without the move;
           the compiled int8 quantize of the migrated payload matches
           the numpy reference and its program holds ``tpu_custom_call``;
           one split gradient matches the unsplit gradient.
  fleet    ``FleetSimulator`` on the serial executor: 64 clients, 4 edges,
           2 cohort signatures, Poisson mobility, int8 migrations,
           2 rounds in sync mode and then in async mode. Every round
           commits, losses are finite, and every migration decodes.
  flops    the device- and server-stage forward FLOPs that
           ``StageCostModel`` reads from the compiler on this backend.

The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import split as split_lib  # noqa: E402
from repro.core.mobility import (MobilityTrace, move_at_round,  # noqa: E402
                                 poisson_moves)
from repro.core.scheduler import FedFlyScheduler  # noqa: E402
from repro.data.datasets import synthetic_cifar10  # noqa: E402
from repro.data.loader import Batcher  # noqa: E402
from repro.data.partition import by_fraction  # noqa: E402
from repro.kernels.int8_codec import (dequantize_packed,  # noqa: E402
                                      dequantize_packed_ref, pack_leaves,
                                      quantize_packed, quantize_packed_ref)
from repro.kernels.int8_codec.int8_codec import BLOCK  # noqa: E402
from repro.launch.compile_cache import enable_compilation_cache  # noqa: E402
from repro.models.vgg import VGG5  # noqa: E402
from repro.optim.optimizers import sgd  # noqa: E402
from repro.optim.schedules import constant  # noqa: E402
from repro.runtime.cluster import (WIFI_75MBPS, StageCostModel,  # noqa: E402
                                   make_testbed_devices, make_testbed_edges)
from repro.sim import (Fleet, FleetSimulator, make_edges,  # noqa: E402
                       make_fleet_specs)

SP = 2
BATCH = 100
ROUNDS = 2
CODECS = ("raw", "int8", "delta")
FLEET_CLIENTS = 64
FLEET_EDGES = 4
# split vs unsplit gradient, float32 matmuls: per leaf,
# max|g_split - g_full| <= GRAD_RTOL * max|g_full|
GRAD_RTOL = 1e-4
# a code may differ from the reference only where x/scale sits this close
# to a rounding tie (the chip's f32 divide need not round like numpy's)
TIE_TOL = 1e-4


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# -- device ------------------------------------------------------------------

def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r}; there is no CPU fallback")
    return dev


# -- testbed -----------------------------------------------------------------

def run_testbed(parts, codec: str, trace, seed: int):
    """One scheduler run; returns it, its history and what it migrated
    (checkpoint, delta base) as the migrator saw it."""
    sched = FedFlyScheduler(
        VGG5(), sgd(momentum=0.9),
        make_testbed_devices([Batcher(p, BATCH, seed=seed) for p in parts]),
        make_testbed_edges(), split_point=SP, lr_schedule=constant(0.01),
        link=WIFI_75MBPS, migration_codec=codec, seed=seed)
    moved = []
    migrate = sched.migrator.migrate

    def capture(ckpt, src, dst, **kw):
        base = (sched.base_registry.base_for(dst)[0]
                if sched.base_registry is not None else None)
        moved.append((ckpt, base))
        return migrate(ckpt, src, dst, **kw)

    sched.migrator.migrate = capture
    sched.initialize()
    hist = sched.run(ROUNDS, trace)
    return sched, hist, moved


def require_compiled_kernel(flat: np.ndarray) -> None:
    """The production quantize, with its default platform auto-detect,
    must lower to a Mosaic kernel, not to the interpreter."""
    text = jax.jit(quantize_packed).lower(jnp.asarray(flat)).as_text()
    check("tpu_custom_call" in text,
          "quantize_packed did not lower to a compiled Mosaic kernel")


def check_quantize(ckpt, base) -> dict:
    """The chip's quantize of the migrated payload (server params +
    momentum) against the numpy reference, plain and residual vs the
    delta codec's base; the chip's dequantize of the reference codes
    against the numpy dequantize."""
    mu = jax.tree.leaves(ckpt.optimizer_state["mu"])
    flat, _ = pack_leaves([np.asarray(x, np.float32) for x in
                           jax.tree.leaves(ckpt.server_params) + mu])
    base_flat, _ = pack_leaves(
        [np.asarray(x, np.float32)
         for x in jax.tree.leaves(base["server_params"])]
        + [np.zeros(np.shape(x), np.float32) for x in mu])
    require_compiled_kernel(flat)
    n = flat.shape[0]
    out = {"payload_floats": int(n)}
    for mode, b in (("plain", None), ("residual", base_flat)):
        q_ref, s_ref = quantize_packed_ref(flat, b)
        q, s = quantize_packed(jnp.asarray(flat),
                               None if b is None else jnp.asarray(b))
        q = np.asarray(q)[:n].astype(np.int32)
        s = np.asarray(s)[:s_ref.shape[0]]
        ulps = np.abs(s.view(np.int32) - s_ref.view(np.int32))
        check(int(ulps.max()) <= 1, f"{mode}: scales differ by "
              f"{int(ulps.max())} ulp from the reference")
        diff = np.abs(q - q_ref.astype(np.int32))
        check(int(diff.max()) <= 1, f"{mode}: a code differs by "
              f"{int(diff.max())} from the reference")
        r = (flat if b is None else flat - b).astype(np.float64)
        t = r / np.repeat(s_ref.astype(np.float64), BLOCK)[:n]
        off = np.flatnonzero(diff)
        check(bool(np.all(np.abs(np.abs(t[off] - np.floor(t[off])) - 0.5)
                          <= TIE_TOL)),
              f"{mode}: a code differs away from a rounding tie")
        x = np.asarray(dequantize_packed(
            jnp.asarray(q_ref), jnp.asarray(s_ref), n,
            None if b is None else jnp.asarray(b)))
        x_ref = dequantize_packed_ref(q_ref, s_ref, n, b)
        # q*s + b may be fused into one rounding on the chip: allow one
        # ulp of the larger operand
        big = np.abs(x_ref) if b is None else np.abs(x_ref) + np.abs(b)
        check(bool(np.all(np.abs(x - x_ref) <= np.spacing(big))),
              f"{mode}: dequantize differs from the reference by > 1 ulp")
        out[mode] = {"codes_off_by_one": int(off.size),
                     "max_scale_ulps": int(ulps.max())}
    return out


def check_split_grad(model, params, batch) -> dict:
    """One split gradient against ``jax.grad`` of the unsplit model on
    the same parameters and batch, with float32 matmuls on both sides."""
    with jax.default_matmul_precision("float32"):
        dev, srv = split_lib.partition_params(model, params, SP)
        loss_s, g_dev, g_srv = jax.jit(
            lambda d, s, b: split_lib.split_value_and_grad(
                model, d, s, b, SP))(dev, srv, batch)
        loss_m, g_m = jax.jit(jax.value_and_grad(model.loss))(params,
                                                              batch)
    g_s = split_lib.merge_grads(model, g_dev, g_srv)
    worst = 0.0
    for a, b in zip(jax.tree.leaves(g_s), jax.tree.leaves(g_m)):
        a, b = np.asarray(a), np.asarray(b)
        worst = max(worst, float(np.max(np.abs(a - b))
                                 / max(float(np.max(np.abs(b))), 1e-30)))
    check(worst <= GRAD_RTOL, f"split gradient off by {worst:.3g} of the "
          f"leaf max (limit {GRAD_RTOL})")
    check(abs(float(loss_s) - float(loss_m)) <= 1e-5 * abs(float(loss_m)),
          f"split loss {float(loss_s)} != unsplit {float(loss_m)}")
    return {"loss": float(loss_m), "max_rel_grad_diff": worst,
            "rtol": GRAD_RTOL}


def testbed_phase(seed: int) -> None:
    train, _ = synthetic_cifar10(n_train=3000, n_test=1, seed=seed)
    parts = by_fraction(train, [0.25, 0.25, 0.25, 0.25])
    trace = MobilityTrace(move_at_round("pi3_1", "edge-A", "edge-B", 1,
                                        fraction=0.5))
    for codec in CODECS:
        t0 = time.perf_counter()
        sched, hist, moved = run_testbed(parts, codec, trace, seed)
        losses = [v for r in hist.rounds for v in r.client_losses.values()]
        check(bool(np.all(np.isfinite(losses))), f"{codec}: loss not finite")
        migs = [m for r in hist.rounds for m in r.migrations]
        check(len(migs) == 1 and len(moved) == 1
              and migs[0].client_id == "pi3_1",
              f"{codec}: expected one migration of pi3_1")
        check(bool(np.isfinite(migs[0].quant_error)),
              f"{codec}: restored state not finite")
        fields = {"codec": codec,
                  "losses": [float(np.mean(list(r.client_losses.values())))
                             for r in hist.rounds],
                  "migrated_bytes": migs[0].nbytes,
                  "quant_error": migs[0].quant_error}
        if codec == "raw":
            # the move changes only where pi3_1 trains, never what
            still, _, _ = run_testbed(parts, codec, None, seed)
            check(all(np.array_equal(a, b) for a, b in zip(
                jax.tree.leaves(sched.global_params),
                jax.tree.leaves(still.global_params))),
                  "raw migration did not resume bit-identically")
            fields["resume_bit_identical"] = True
            batch = {k: jnp.asarray(v) for k, v in
                     sched.devices["pi3_1"].batcher.batch_at(0, 0).items()}
            fields["split_grad"] = check_split_grad(
                sched.model, sched.global_params, batch)
        if codec == "delta":
            fields["quantize"] = check_quantize(*moved[0])
        fields["wall_s"] = time.perf_counter() - t0
        log("testbed", **fields)


# -- fleet -------------------------------------------------------------------

def fleet_phase(seed: int) -> None:
    edge_ids = [f"edge-{i}" for i in range(FLEET_EDGES)]
    specs = make_fleet_specs(FLEET_CLIENTS, edge_ids, cohorts=2)
    trace = MobilityTrace(poisson_moves([s.client_id for s in specs],
                                        edge_ids, total_rounds=ROUNDS,
                                        rate_per_round=0.1, seed=seed))
    for mode in ("sync", "async"):
        t0 = time.perf_counter()
        fleet = Fleet(VGG5(), sgd(momentum=0.9), specs, split_point=SP,
                      lr_schedule=constant(0.01), seed=seed)
        check(len(fleet.cohorts) == 2, "expected 2 cohort signatures")
        sim = FleetSimulator(fleet, make_edges(FLEET_EDGES), trace=trace,
                             mode=mode, migration_codec="int8")
        res = sim.run(ROUNDS)
        updates = [r["n_updates"] for r in res.rounds]
        check(not any(r.get("skipped_round") for r in res.rounds),
              f"{mode}: a round was skipped")
        if mode == "sync":
            check(updates == [FLEET_CLIENTS] * ROUNDS,
                  f"sync: rounds committed {updates} updates")
        else:
            check(sum(updates) == FLEET_CLIENTS * ROUNDS,
                  f"async: {sum(updates)} updates applied")
        check(all(np.isfinite(r["mean_loss"]) for r in res.rounds),
              f"{mode}: loss not finite")
        count = res.migration_summary["count"]
        reports = sim.migrator.reports
        check(count > 0 and len(reports) == count,
              f"{mode}: {count} migrations, {len(reports)} decoded")
        check(all(r.codec == "int8" and np.isfinite(r.quant_error)
                  for r in reports), f"{mode}: a migration did not decode")
        log("fleet", mode=mode, updates=updates, migrations=count,
            losses=[r["mean_loss"] for r in res.rounds],
            wall_s=time.perf_counter() - t0)


# -- stage FLOPs -------------------------------------------------------------

def flops_phase(seed: int) -> None:
    model = VGG5()
    params = model.init(jax.random.PRNGKey(seed))
    dev, srv = split_lib.partition_params(model, params, SP)
    batch = {"images": jnp.zeros((BATCH, 32, 32, 3), jnp.float32),
             "labels": jnp.zeros((BATCH,), jnp.int32)}
    dflops, sflops, sbytes = StageCostModel().costs(model, dev, srv, batch,
                                                    SP)
    check(dflops > 0 and sflops > 0, "the compiler reported no FLOPs")
    log("flops", split_point=SP, batch=BATCH, device_fwd=dflops,
        server_fwd=sflops, smashed_bytes=sbytes)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = require_tpu()
    log("device", kind=dev.device_kind, count=len(jax.devices()),
        jax=jax.__version__, compile_cache=enable_compilation_cache())
    testbed_phase(args.seed)
    fleet_phase(args.seed)
    flops_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
