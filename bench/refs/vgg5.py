"""Plain reference for VGG-5 split training (FedFly, arXiv:2111.01516).

Straightforward ``jax.numpy``: the whole model in one function, no
split, no kernels, no batching tricks. Every convolution and matrix
product runs at the precision asked for: ``"highest"``, so that float32
means float32 on a TPU, or ``"default"``, the precision the
configuration states (one bfloat16 pass on a TPU, float32 elsewhere).
It imports nothing of the program. Its weights come from the seed by
the recipe the configuration states (He-normal, zero bias, one
``jax.random.split`` key per layer).

``dtype=jnp.bfloat16`` runs the same mathematics with parameters,
activations, gradients and optimizer state held in bfloat16: the
control, one precision step below what the configuration states.

What every reference gives the harness, each taking the configuration
as its file states it:

``init(seed, config)``        the weights, from the seed.
``train_steps(params, batches, config, dtype, precision)``  the steps
                              over ``batches``, the optimizer read from
                              the configuration.
``train_flops_per_sample(config)``  operations a sample of training
                              needs, for ``split_step.mfu``.

Here the model is the configuration's ``layers`` over ``image``-shaped
inputs, the optimizer SGD with its ``lr`` and ``momentum``, and a batch
holds ``images`` and ``labels``.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

import flops

PRECISION = {"highest": jax.lax.Precision.HIGHEST,
             "default": jax.lax.Precision.DEFAULT}


def _layers(config: Dict[str, Any]) -> Tuple[Tuple, ...]:
    return tuple(tuple(l) for l in config["layers"])


def train_flops_per_sample(config: Dict[str, Any]) -> int:
    """Three forward passes of the configuration's shapes
    (``bench/flops.py``)."""
    return flops.train_per_sample(config["layers"], config["image"])


def init(seed: int, config: Dict[str, Any]) -> List[Dict[str, jax.Array]]:
    layers = _layers(config)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(layers))
    params = []
    for k, layer in zip(keys, layers):
        if layer[0] == "conv":
            _, cin, cout, _ = layer
            w = jax.random.normal(k, (3, 3, cin, cout), jnp.float32)
            w = w * jnp.sqrt(2.0 / (9 * cin))
        else:
            _, fin, fout = layer
            w = jax.random.normal(k, (fin, fout), jnp.float32)
            w = w * jnp.sqrt(2.0 / fin)
        params.append({"w": w, "b": jnp.zeros((w.shape[-1],), jnp.float32)})
    return params


def logits(params, images, layers, precision="highest"):
    prec = PRECISION[precision]
    x = images
    for i, (p, layer) in enumerate(zip(params, layers)):
        if layer[0] == "conv":
            x = jax.lax.conv_general_dilated(
                x, p["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=prec)
            x = jnp.maximum(x + p["b"], 0)
            if layer[3]:
                n, h, w, c = x.shape
                x = x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
        else:
            x = jnp.dot(x.reshape(x.shape[0], -1), p["w"],
                        precision=prec) + p["b"]
            if i < len(layers) - 1:
                x = jnp.maximum(x, 0)
    return x


def loss(params, images, labels, layers, precision="highest"):
    z = logits(params, images, layers, precision)
    lp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], axis=-1))


def train_steps(params, batches, config: Dict[str, Any],
                dtype=jnp.float32, precision="highest"):
    """SGD with momentum (mu <- momentum*mu + g; p <- p - lr*mu) over
    ``batches``, from zero momentum. Returns the loss of each step, the
    first step's gradient and the parameters after the last step, all
    as float32."""
    layers = _layers(config)
    lr, momentum = float(config["lr"]), float(config["momentum"])
    cast = lambda t: jax.tree.map(lambda x: x.astype(dtype), t)
    p = cast(params)
    mu = jax.tree.map(jnp.zeros_like, p)
    vg = jax.value_and_grad(loss)
    losses, g1 = [], None
    for b in batches:
        val, g = vg(p, b["images"].astype(dtype), b["labels"], layers,
                    precision)
        g1 = g if g1 is None else g1
        mu = jax.tree.map(lambda m, gi: (momentum * m + gi).astype(dtype),
                          mu, g)
        p = jax.tree.map(lambda x, m: (x - lr * m).astype(dtype), p, mu)
        losses.append(val.astype(jnp.float32))
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    return jnp.stack(losses), f32(g1), f32(p)
