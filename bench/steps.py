"""What the training check reads from the program's own step, and the
reference run it is compared with. Both keep on the host only what
``check.training_numbers`` compares.

``StepCapture`` sits in front of the scheduler's jitted split train
step while set-up drives the first steps through the window's own call.
For each stream (a client) it reads the first three calls. Before a call
it copies the batch to the host, and before the first call the
parameters going in, so a step that donates its inputs leaves the
capture whole. After a call it keeps the loss; after the first, the
per-leaf norms of the gradient the optimizer got; after the third, the
per-leaf norms of the parameters' change over the three calls, and the
host copy of the first parameters goes. No device array outlives
``before`` or ``record``, and nothing is read once capture is off.

Its owner, the cell (``cellbase``), gives ``step_inputs(args)`` ->
((dev, srv) params, batch), ``step_outputs(outs)`` -> ((dev, srv) new
params, (dev, srv) new optimizer state, loss), ``host_leaves((dev,
srv))``, the full parameter tree's leaves on the host in the reference's
order, and ``first_grad(opt_state)``, the gradient an optimizer state
holds after one step from its init.

``reference_readings`` runs the plain reference (or its control, or a
planted fault) over the same batches, every key of them, one stream at
a time, and reduces each stream's outputs to the same norms before the
next stream runs; the reference reads its model and optimizer from the
configuration.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List

import numpy as np

import check

STEPS = 3


class StepCapture:
    def __init__(self, owner):
        self.owner = owner
        self.streams: Dict[Any, Dict[str, Any]] = {}
        self.on = True
        self.closed = set()

    def _open(self, stream) -> bool:
        s = self.streams.get(stream)
        return (self.on and stream not in self.closed
                and (s is None or len(s["losses"]) < STEPS))

    def before(self, stream, args: tuple) -> None:
        if not self._open(stream):
            return
        params, batch = self.owner.step_inputs(args)
        s = self.streams.setdefault(stream, {"losses": [], "batches": []})
        if not s["losses"]:
            s["p0"] = self.owner.host_leaves(params)
        s["batch"] = {k: np.asarray(v) for k, v in batch.items()}

    def record(self, stream, outs: tuple) -> None:
        s = self.streams.get(stream)
        if s is None or "batch" not in s:      # ``before`` took no copy
            return
        params, opt, loss = self.owner.step_outputs(outs)
        s["batches"].append(s.pop("batch"))
        s["losses"].append(float(loss))
        if len(s["losses"]) == 1:
            s["g1"] = check.leaf_norms(self.owner.host_leaves(
                tuple(self.owner.first_grad(o) for o in opt)))
        if len(s["losses"]) == STEPS:
            p0 = s.pop("p0")
            s["delta"] = check.leaf_norms(
                np.asarray(b, np.float64) - a
                for a, b in zip(p0, self.owner.host_leaves(params)))

    def close(self, stream) -> None:
        """No more steps of this stream count (its state is about to be
        migrated, which the reference does not model)."""
        self.closed.add(stream)

    def complete(self) -> bool:
        return bool(self.streams) and all(
            len(s["losses"]) == STEPS for s in self.streams.values())

    def _ordered(self) -> List[Dict[str, Any]]:
        return [self.streams[k] for k in sorted(self.streams)]

    def batches(self) -> List[List[Dict[str, np.ndarray]]]:
        """Each stream's three host batches, streams in sorted order."""
        return [s["batches"] for s in self._ordered()]

    def readings(self) -> Dict[str, np.ndarray]:
        """``losses`` (S, R); ``g1`` and ``delta`` (R, L) leaf norms."""
        ss = self._ordered()
        return {"losses": np.array([s["losses"] for s in ss], np.float64).T,
                "g1": np.stack([s["g1"] for s in ss]),
                "delta": np.stack([s["delta"] for s in ss])}


_REFERENCE = {}


def _reference_fn(ref, config, dtype, precision):
    """One jitted program per (reference, configuration, dtype,
    precision), called once per stream: the weights and the batches are
    arguments, so every seed and stream runs the same program and a run
    after the first finds it in the compilation cache."""
    import jax
    key = (ref.__name__, json.dumps(config, sort_keys=True), str(dtype),
           precision)
    if key not in _REFERENCE:
        def one(params, batches):
            return ref.train_steps(params, batches, config, dtype,
                                   precision)
        _REFERENCE[key] = jax.jit(one)
    return _REFERENCE[key]


def reference_readings(ref, config: Dict[str, Any], seed: int,
                       batches: List[List[Dict[str, np.ndarray]]],
                       precision: str = "highest", control: bool = False,
                       half_batch: bool = False) -> Dict[str, np.ndarray]:
    """The reference from the seed over each stream's captured batches
    (``StepCapture.batches``): float32 with its products at
    ``precision`` (``"highest"`` or the configuration's ``"default"``),
    or wholly in bfloat16 (``control``), or over the first half of each
    batch only (``half_batch``, a planted fault). Readings as
    ``StepCapture.readings``."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.bfloat16 if control else jnp.float32
    params = ref.init(seed, config)
    p0 = [np.asarray(x) for x in jax.tree.leaves(params)]
    fn = _reference_fn(ref, config, dtype, precision)
    losses, g1, delta = [], [], []
    for stream in batches:
        feed = [{k: jnp.asarray(v[: len(v) // 2] if half_batch else v)
                 for k, v in b.items()} for b in stream]
        loss, g, p3 = fn(params, feed)
        losses.append(np.asarray(loss, np.float64))
        g1.append(check.leaf_norms(jax.tree.leaves(g)))
        delta.append(check.leaf_norms(
            np.asarray(x, np.float64) - a
            for a, x in zip(p0, jax.tree.leaves(p3))))
        del feed, loss, g, p3
    return {"losses": np.stack(losses, axis=1), "g1": np.stack(g1),
            "delta": np.stack(delta)}
