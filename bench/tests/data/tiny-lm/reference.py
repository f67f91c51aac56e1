"""Test-only reference for the small token model of ``config.json``.

It calls the program's own monolithic loss (``TransformerLM.loss``,
unsplit), because the test that uses it checks the harness's plumbing
for a configuration that is not VGG-5: batches with other keys, a
parameter tree of dicts with stacked layers, and a driver that
subclasses the testbed's. A benchmark configuration brings a plain
reference that imports nothing of the program. The harness's tests copy
this file to ``bench/refs/tinylm.py`` of a scratch checkout.

The contract is ``bench/refs/vgg5.py``'s: ``init``, ``train_steps`` and
``train_flops_per_sample``, each taking the configuration.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def _model(config: Dict[str, Any]):
    """The registry's reduced size, or the configuration's ``sizes``."""
    from repro.models import registry
    arch = registry.get_config(config["arch"])
    sizes = config.get("sizes")
    return registry.build_model(
        arch.replace(**sizes) if sizes else registry.make_reduced(arch))


def init(seed: int, config: Dict[str, Any]):
    return _model(config).init(jax.random.PRNGKey(seed))


def train_steps(params, batches, config: Dict[str, Any],
                dtype=jnp.float32, precision="highest"):
    """SGD with momentum (mu <- momentum*mu + g; p <- p - lr*mu) over
    ``batches``, from zero momentum: the loss of each step, the first
    step's gradient and the parameters after the last step, as
    float32."""
    model = _model(config)
    lr, momentum = float(config["lr"]), float(config["momentum"])
    p = jax.tree.map(lambda x: x.astype(dtype), params)
    mu = jax.tree.map(jnp.zeros_like, p)
    losses, g1 = [], None
    with jax.default_matmul_precision(precision):
        for b in batches:
            val, g = jax.value_and_grad(model.loss)(p, b)
            g1 = g if g1 is None else g1
            mu = jax.tree.map(
                lambda m, gi: (momentum * m + gi).astype(dtype), mu, g)
            p = jax.tree.map(lambda x, m: (x - lr * m).astype(dtype), p, mu)
            losses.append(val.astype(jnp.float32))
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    return jnp.stack(losses), f32(g1), f32(p)


def train_flops_per_sample(config: Dict[str, Any]) -> int:
    """Three forward passes over ``seq_len`` tokens: two operations per
    weight of every product (attention's four, the SwiGLU's three, the
    head) and the causal attention's scores and values counted in
    full."""
    arch = _model(config).cfg
    d, s = arch.d_model, config["seq_len"]
    qo = 2 * d * arch.num_heads * arch.head_dim
    kv = 2 * d * arch.num_kv_heads * arch.head_dim
    per_layer = 2 * (qo + kv + 3 * d * arch.d_ff) \
        + 4 * s * arch.num_heads * arch.head_dim
    forward = arch.num_layers * per_layer + 2 * d * arch.vocab_size
    return 3 * s * forward
