"""Compile rehearsals for one TPU v5e chip, with no chip attached.

The TPU compiler is installed beside the CPU backend, so it can compile
for a chip that is only described: it refuses what the chip would
refuse (block shapes Mosaic cannot tile, programs that do not fit),
which interpret mode never sees. Nothing runs, so these cases say
nothing about results or times.

The ``v5e:2x2`` topology is described inside a module fixture, never
at import: describing it loads the TPU library, which one process at a
time may hold. Kernels are compiled with ``interpret=False`` because the
platform auto-detect picks the interpreter on a CPU backend. The
persistent compilation cache is off around these compiles: an entry
written for a described chip cannot be read back without one.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import split as split_lib
from repro.core.scheduler import FedFlyScheduler
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_codec import dequantize_packed, quantize_packed
from repro.models.vgg import VGG5
from repro.optim.optimizers import sgd
from repro.optim.schedules import constant
from repro.sim.fleet import Cohort

SP = 2                                   # the paper's default split point
SRV_FLOATS = 169_418                     # VGG-5 server stage at SP2
PAYLOAD = 2 * SRV_FLOATS                 # server params + momentum
LARGE = 64 * 2 ** 20                     # 64 Mi elements


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _stage_shapes(model, opt, sharding, replicas=None):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    dev, srv = split_lib.partition_params(model, params, SP)
    trees = [dev, srv, jax.eval_shape(opt.init, dev),
             jax.eval_shape(opt.init, srv)]
    if replicas is not None:
        trees = [jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            (replicas,) + s.shape, s.dtype), t) for t in trees]
    return [_on(sharding, t) for t in trees]


def _batch(sharding, lead):
    return _on(sharding, {
        "images": jax.ShapeDtypeStruct(lead + (32, 32, 3), jnp.float32),
        "labels": jax.ShapeDtypeStruct(lead, jnp.int32)})


@pytest.mark.parametrize("n", [PAYLOAD, LARGE], ids=["vgg5_sp2", "64Mi"])
@pytest.mark.parametrize("residual", [False, True], ids=["plain", "delta"])
def test_int8_codec_compiles(one_chip, n, residual):
    x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    base = x if residual else None

    def roundtrip(x, base=None):
        q, s = quantize_packed(x, base, interpret=False)
        return dequantize_packed(q, s, n, base, interpret=False)

    args = (x, base) if residual else (x,)
    quant = jax.jit(lambda *a: quantize_packed(*a, interpret=False))
    for fn in (quant, jax.jit(roundtrip)):
        text = fn.lower(*args).compile().as_text()
        assert "tpu_custom_call" in text


def test_scheduler_split_step_compiles(one_chip):
    """The jitted split-train step ``FedFlyScheduler`` runs per batch,
    at the paper's batch of 100."""
    model, opt = VGG5(), sgd(momentum=0.9)
    sched = FedFlyScheduler(model, opt, [], [], split_point=SP,
                            lr_schedule=constant(0.01))
    sched._build_step()
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = sched._step.lower(*_stage_shapes(model, opt, one_chip),
                                 _batch(one_chip, (100,)), lr).compile()
    assert compiled.memory_analysis() is not None


def test_fleet_cohort_step_compiles(one_chip):
    """The fleet's vmapped cohort step: 4 replicas at batch 16."""
    model, opt = VGG5(), sgd(momentum=0.9)
    cohort = Cohort((16, 2), model, opt, SP, replicas=4, seed=0)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = cohort._step.lower(
        *_stage_shapes(model, opt, one_chip, replicas=4),
        _batch(one_chip, (4, 16)), lr).compile()
    assert compiled.memory_analysis() is not None


def test_flash_attention_compiles(one_chip):
    """GQA flash attention at a real width: 16 query heads over 8 KV
    heads, head dim 128, 4096 positions, bf16."""
    q = jax.ShapeDtypeStruct((1, 16, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, 4096, 128), jnp.bfloat16,
                              sharding=one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, 0, 0.0,
                                                 128, 128, False))
    assert "tpu_custom_call" in fn.lower(q, kv, kv).compile().as_text()
