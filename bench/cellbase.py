"""What every driver's cell shares: what it keeps for the comparison,
and the numbers it turns that into. It keeps host data only, and only
what it compares, so the check takes no device memory from the program
and a step that donates its inputs leaves it whole.

A driver's ``Cell`` subclasses ``CellBase`` and gives the parts that
depend on its model:

``merge(dev, srv)``       the full parameter tree from a device stage and
                          a server stage, leaves in the order of the
                          reference's (``bench/refs/<reference>.py``); it
                          serves the training check and the fold sample,
                          and runs on host copies (``host_leaves``).
``first_grad(opt_state)`` the gradient an optimizer state holds after one
                          step from its init, as the optimizer got it.

and fills in, while its system runs:

``capture``  the first three steps of each client, reduced to the
             numbers the training check compares (``steps.StepCapture``).
``fold``     one fold, the warm-up round's, reduced as it is taken
             (``take_fold``) to the two numbers compared: ``{"program",
             "control"}``, its ``fold_err`` and the control's.
``migs``     a reservoir sample of the window's migrations
             (``sample_migration``): the host tree the move's own fetch
             made, the base, the restored state's host fields and, where
             the codec ran the packed quantize, its inputs and outputs.
             It keeps ``SAMPLED_MIGRATIONS`` where they fit in
             ``MIGRATION_SAMPLE_BYTES`` of host memory, else as many as
             fit, and at least one, so a single migration larger than
             the budget is kept whole all the same.

``numbers`` compares what the program produced; ``variants`` puts the
control (the reference one precision step down) or a planted fault in
the program's place, one part at a time, for the readings the limits are
set from.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import check
from steps import StepCapture, reference_readings

SAMPLED_MIGRATIONS = 8
#: host bytes the migration sample may hold (a sample's trees, base,
#: restored state and quantize codes, each array counted once); one
#: sample is kept even where it alone is larger
MIGRATION_SAMPLE_BYTES = 2 << 30
CHECKPOINT_PARTS = ("server_params", "optimizer_state", "last_grads",
                    "scalars")
#: the training comparison's two references: the configuration's stated
#: precision (names as they are) and float32 as written (``_f32``)
PRECISIONS = (("default", ""), ("highest", "_f32"))
#: what ``variants`` can put in the program's place
VARIANTS = ("control_step", "control_fold", "control_codec", "half_batch")


def _bf16(a) -> np.ndarray:
    import ml_dtypes
    return np.asarray(a, ml_dtypes.bfloat16).astype(np.float64)


def _host_bytes(tree) -> int:
    """Bytes of the distinct arrays among a tree's leaves."""
    import jax
    seen = {id(x): x for x in jax.tree.leaves(tree) if hasattr(x, "nbytes")}
    return sum(int(x.nbytes) for x in seen.values())


class CellBase:
    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, spans, tick: Callable[[int], None] = None,
                 reference=None):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.spans = spans
        self.tick = tick or (lambda n: None)
        self.ref = reference
        self.step_program = config["step_program"]
        self.rng = np.random.default_rng([seed, 5])
        self.capture = StepCapture(self)
        self.migs: List[Dict[str, Any]] = []
        self.mig_capacity: Optional[int] = None
        self.n_migs_seen = 0
        self.fold: Optional[Dict[str, float]] = None

    @staticmethod
    def step_inputs(args):
        """A split train step's (dev, srv, dev_opt, srv_opt, batch, lr):
        its parameters and its batch."""
        return (args[0], args[1]), args[4]

    @staticmethod
    def step_outputs(outs):
        """Its (dev, srv, dev_opt, srv_opt, loss, ...): new parameters,
        new optimizer state, loss."""
        return (outs[0], outs[1]), (outs[2], outs[3]), outs[4]

    def merge(self, dev, srv):
        raise NotImplementedError("a driver's Cell gives merge(dev, srv)")

    def first_grad(self, opt_state):
        raise NotImplementedError("a driver's Cell gives first_grad(state)")

    def host_leaves(self, pair) -> List[np.ndarray]:
        """The full tree of a (dev, srv) pair as host arrays: both stages
        are copied to the host first and merged there."""
        import jax
        dev, srv = (jax.tree.map(np.asarray, t) for t in pair)
        with jax.default_device(jax.devices("cpu")[0]):
            return [np.asarray(x) for x in jax.tree.leaves(
                self.merge(dev, srv))]

    def sample_migration(self, sent, base, restored, quant=None) -> None:
        """``sent``: the host tree the move packed (``to_tree``);
        ``restored``: the restored checkpoint's fields; ``quant``:
        (leaves, bases, codes, scales) of the packed quantize this
        migration ran, if it ran one. All host data."""
        self.n_migs_seen += 1
        rec = {"sent": sent, "base": base, "restored": restored,
               "quant": quant}
        if self.mig_capacity is None:
            self.mig_capacity = max(1, min(
                SAMPLED_MIGRATIONS,
                MIGRATION_SAMPLE_BYTES // max(_host_bytes(rec), 1)))
        if len(self.migs) < self.mig_capacity:
            self.migs.append(rec)
            return
        j = int(self.rng.integers(0, self.n_migs_seen))
        if j < self.mig_capacity:
            self.migs[j] = rec

    # -- the numbers --------------------------------------------------------

    def training_readings(self, mode: str = "program") -> Dict[str, float]:
        """Readings of the program (``program``), or of the reference put
        in its place in bfloat16 (``control``) or over half of each batch
        (``half_batch``), against the float32 reference at the stated
        precision and at ``HIGHEST``."""
        batches = self.capture.batches()
        out: Dict[str, float] = {}
        for precision, suffix in PRECISIONS:
            ref = reference_readings(self.ref, self.config, self.seed,
                                     batches, precision)
            if mode == "program":
                got = self.capture.readings()
            else:
                got = reference_readings(
                    self.ref, self.config, self.seed, batches, precision,
                    control=mode == "control",
                    half_batch=mode == "half_batch")
            out.update(check.training_numbers(got, ref, suffix))
        return out

    def take_fold(self, trees: List[List[np.ndarray]], weights,
                  out: List[np.ndarray]) -> None:
        """Reduce one fold to its numbers: ``out``, the committed fold of
        ``trees`` (host leaves) by ``weights``, against a float64 numpy
        fold of the same inputs, leaf by leaf; the control folds
        bfloat16-rounded inputs instead."""
        def ref(cast=lambda x: x):
            for j in range(len(trees[0])):
                yield check.fedavg_leaf([cast(t[j]) for t in trees], weights)
        self.fold = {"program": check.fold_err(out, ref()),
                     "control": check.fold_err(
                         (_bf16(x) for x in ref(_bf16)), ref())}

    def fold_number(self, control: bool = False) -> float:
        """``fold_err`` of the fold ``take_fold`` kept, or its control's."""
        if self.fold is None:
            return math.inf
        return self.fold["control" if control else "program"]

    def _mig_leaves(self, m):
        import jax
        from repro.core.checkpoint import EdgeCheckpoint
        sent = m["sent"]
        rest = EdgeCheckpoint(**m["restored"]).to_tree()
        s_l, r_l, b_l = [], [], []
        for k in CHECKPOINT_PARTS:
            if k not in sent:
                continue
            ls = jax.tree.leaves(sent[k])
            s_l += ls
            r_l += jax.tree.leaves(rest[k])
            if k == "server_params" and m["base"] is not None:
                b_l += jax.tree.leaves(m["base"]["server_params"])
            else:
                b_l += [None] * len(ls)
        return s_l, r_l, b_l

    def codec_number(self, control: bool = False) -> Dict[str, float]:
        """``raw``: elements not restored bit for bit. ``int8``/``delta``:
        the worst restore error in half quantization steps and, where the
        migration ran the packed quantize, its codes and scales against
        the reference. ``control`` restores from, and quantizes to, int4
        codes instead (raw has no lower step)."""
        codec = self.traffic["codec"]
        name = "raw_mismatch" if codec == "raw" else "codec_err"
        if control and codec == "raw":
            return {}
        if not self.migs:
            return {name: math.inf}
        out = {name: 0.0}
        for m in self.migs:
            s, r, b = self._mig_leaves(m)
            if codec == "raw":
                out[name] += check.raw_mismatch(s, r)
                continue
            if control:
                r = check.requantize(s, b, codec, bits=4)
            out[name] = max(out[name], check.codec_err(s, r, b, codec))
            if m["quant"] is not None:
                leaves, bases, codes, scales = m["quant"]
                if control:
                    codes, scales = check.quantize_blocks(
                        check.pack(leaves, leaves),
                        None if bases is None else check.pack(bases, leaves),
                        qmax=7)
                for k, v in check.kernel_numbers(leaves, bases, codes,
                                                 scales).items():
                    out[k] = max(out.get(k, 0), v)
        out[name] = float(out[name])
        return out

    def numbers(self) -> Dict[str, float]:
        out = self.training_readings("program")
        out["fold_err"] = self.fold_number()
        out.update(self.codec_number())
        return out

    def variants(self, names, program: Dict[str, float]
                 ) -> Dict[str, Dict[str, float]]:
        """For each name in ``VARIANTS``: the program's numbers with one
        part put in its place. ``control_step``: the training steps in
        bfloat16; ``control_fold``: the fold of bfloat16-rounded inputs;
        ``control_codec``: int4 codes; ``half_batch``: the training steps
        over the first half of each batch."""
        out = {}
        for name in names:
            nums = dict(program)
            if name == "control_step":
                nums.update(self.training_readings("control"))
            elif name == "control_fold":
                nums["fold_err"] = self.fold_number(control=True)
            elif name == "control_codec":
                part = self.codec_number(control=True)
                if not part:
                    continue
                nums.update(part)
            elif name == "half_batch":
                nums.update(self.training_readings("half_batch"))
            else:
                raise KeyError(f"no variant named {name!r}")
            out[name] = nums
        return out
