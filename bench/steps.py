"""What the training check reads from the program's own step, and the
reference run it is compared with.

``StepCapture`` sits in front of the scheduler's jitted split train
step while set-up drives the first steps through the window's own call.
For each stream (a client) it keeps the first three calls: the parameters
going in, the batch, and what came out. Nothing is copied to the host
and nothing is synchronised, so the step runs as it does in the window.

``program_readings`` turns a capture into the numbers ``check``
compares, through two functions the cell supplies: ``merge(dev, srv)``,
the full parameter tree in the reference's leaf order, and
``first_grad(opt_state)``, the gradient an optimizer state holds after
one step from its init. ``reference_readings`` runs the plain reference
(or its control, or a planted fault) over the same batches, every key of
them; the reference reads its model and optimizer from the
configuration.
"""
from __future__ import annotations

import json
from typing import Any, Callable, Dict, List

import numpy as np

STEPS = 3


class StepCapture:
    def __init__(self, unpack: Callable[[tuple, tuple], Dict[str, Any]]):
        """``unpack(args, outs)`` -> {"params": (dev, srv), "batch",
        "new_params": (dev, srv), "new_opt": (dev, srv), "loss"}."""
        self.unpack = unpack
        self.streams: Dict[Any, List[Dict[str, Any]]] = {}
        self.on = True
        self.closed = set()

    def record(self, stream, args: tuple, outs: tuple) -> None:
        calls = self.streams.setdefault(stream, [])
        if self.on and len(calls) < STEPS and stream not in self.closed:
            calls.append(self.unpack(args, outs))

    def close(self, stream) -> None:
        """No more steps of this stream count (its state is about to be
        migrated, which the reference does not model)."""
        self.closed.add(stream)

    def complete(self) -> bool:
        return bool(self.streams) and all(
            len(c) == STEPS for c in self.streams.values())


def _leaves(tree) -> List[np.ndarray]:
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _stack(per_stream: List[List[np.ndarray]]) -> List[np.ndarray]:
    """Leaves of every stream stacked on one leading client axis."""
    return [np.stack(xs) for xs in zip(*per_stream)]


def program_readings(streams: List[List[Dict[str, Any]]], merge: Callable,
                     first_grad: Callable) -> Dict[str, Any]:
    """(losses (S, R), g1 leaves (R, ...), delta leaves (R, ...)) over
    every stream; ``merge`` and ``first_grad`` as the module says."""
    def full(pair):
        return _leaves(merge(*pair))

    p0 = _stack([full(c[0]["params"]) for c in streams])
    p3 = _stack([full(c[-1]["new_params"]) for c in streams])
    losses = np.array([[float(c[k]["loss"]) for c in streams]
                       for k in range(STEPS)])
    return {
        "losses": losses,
        "g1": _stack([full(tuple(first_grad(o) for o in c[0]["new_opt"]))
                      for c in streams]),
        "delta": [b.astype(np.float64) - a for a, b in zip(p0, p3)],
    }


_REFERENCE = {}


def _reference_fn(ref, config, dtype, precision):
    """One jitted program per (reference, configuration, dtype,
    precision): the weights and the batches are arguments, so every seed
    runs the same program and a run after the first finds it in the
    compilation cache."""
    import jax
    key = (ref.__name__, json.dumps(config, sort_keys=True), str(dtype),
           precision)
    if key not in _REFERENCE:
        def one(params, batches):
            return ref.train_steps(params, batches, config, dtype,
                                   precision)
        _REFERENCE[key] = jax.jit(jax.vmap(one, in_axes=(None, 0)))
    return _REFERENCE[key]


def reference_readings(ref, config: Dict[str, Any], seed: int,
                       streams: List[List[Dict[str, Any]]],
                       precision: str = "highest", control: bool = False,
                       half_batch: bool = False) -> Dict[str, Any]:
    """The reference from the seed over the captured batches of every
    stream: float32 with its products at ``precision`` (``"highest"`` or
    the configuration's ``"default"``), or wholly in bfloat16
    (``control``), or over the first half of each batch only
    (``half_batch``, a planted fault)."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.bfloat16 if control else jnp.float32
    params = ref.init(seed, config)
    batches = []
    for k in range(STEPS):
        b = {}
        for name in streams[0][k]["batch"]:
            x = np.stack([np.asarray(c[k]["batch"][name]) for c in streams])
            if half_batch:
                x = x[:, : x.shape[1] // 2]
            b[name] = jnp.asarray(x)
        batches.append(b)
    fn = _reference_fn(ref, config, dtype, precision)
    losses, g1, p3 = fn(params, batches)
    p0 = [np.asarray(x, np.float64)[None] for x in jax.tree.leaves(params)]
    return {
        "losses": np.asarray(losses, np.float64).T,
        "g1": [np.asarray(x) for x in jax.tree.leaves(g1)],
        "delta": [np.asarray(x, np.float64) - a
                  for a, x in zip(p0, jax.tree.leaves(p3))],
    }
