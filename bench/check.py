"""The comparisons that decide ``correct``.

Each function returns one number; ``judge`` holds every number against
its limit from ``limits/<workload>.json``. The numbers:

``loss_gap``    worst |loss_prog - loss_ref| / |loss_ref| over the first
                three steps of every captured client, against the plain
                reference at the configuration's stated precision
                (products at JAX's default precision).
``grad_gap``    the first gradient as the optimizer got it (momentum after
                one step from zero), by the worst leaf: the gap between
                the program's and the reference's leaf norms over the
                larger of that reference leaf's norm and the median leaf
                norm.
``update_gap``  the same for the parameters' change over three steps.
                Leaves whose reference gradient is under a thousandth of
                the median leaf's are left out (they move by round-off).
``*_f32``       the same three against the reference with every product
                at ``HIGHEST``: float32 as written.
``fold_err``    the committed global model of the warm-up round, the
                window's fold program at its shapes, against a float64
                numpy fold of the same updates, leaf by leaf: worst leaf
                of max|diff| / max|ref|.
``codec_err``   a migrated state as restored against the state sent:
                worst |restored - sent| over half the codec's quantization
                step (plus two float32 spacings of the value). 1 is the
                codec's own bound.
``raw_mismatch`` elements of a raw migration not restored bit for bit.
``kernel_code_off``  codes of the packed int8 quantize, as the migration
                produced them, that differ from the numpy reference by
                more than one, or by one away from a rounding tie.
``kernel_scale_ulps`` the widest gap, in float32 ulps, between its block
                scales and the reference's.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

BLOCK = 1024          # the delta codec's quantization block (elements)
MIN_QUANT_SIZE = 64   # leaves this small or smaller ship raw
NEGLIGIBLE_GRAD = 1e-3
# a kernel code may differ from the reference by one only where x/scale
# sits this close to a rounding tie (the chip's float32 divide need not
# round like numpy's)
TIE_TOL = 1e-4


def leaf_norms(leaves: Iterable[Any]) -> np.ndarray:
    """(L,) float64 norm of each leaf, one leaf at a time: ``leaves``
    may be a generator, so no whole tree is held in float64."""
    return np.array([np.sqrt(np.sum(np.square(
        np.asarray(x, np.float64).reshape(-1)))) for x in leaves])


def norm_gap(prog: np.ndarray, ref: np.ndarray,
             keep: Optional[np.ndarray] = None) -> float:
    """``prog``/``ref``: (R, L) leaf norms per client."""
    a, r = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    med = np.median(r, axis=1, keepdims=True)
    gap = np.abs(a - r) / np.maximum(np.maximum(r, med), 1e-30)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    return float(np.max(gap))


def training_numbers(prog: Dict[str, Any], ref: Dict[str, Any],
                     suffix: str = "") -> Dict[str, float]:
    """``prog``/``ref``: ``losses`` (S, R), ``g1`` and ``delta`` (R, L)
    leaf norms (``leaf_norms``), leaves in one order."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    g_ref = np.asarray(ref["g1"], np.float64)
    keep = g_ref >= NEGLIGIBLE_GRAD * np.median(g_ref, axis=1,
                                                keepdims=True)
    return {
        "loss_gap" + suffix: float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap" + suffix: norm_gap(prog["g1"], ref["g1"]),
        "update_gap" + suffix: norm_gap(prog["delta"], ref["delta"], keep),
    }


def fold_err(prog: Iterable[np.ndarray], ref: Iterable[np.ndarray]
             ) -> float:
    """Worst leaf of max|diff| / max|ref|, one leaf at a time."""
    out = 0.0
    for a, r in zip(prog, ref):
        a = np.asarray(a, np.float64)
        r = np.asarray(r, np.float64)
        out = max(out, float(np.max(np.abs(a - r)))
                  / max(float(np.max(np.abs(r))), 1e-30))
    return out


def fedavg_leaf(xs: Sequence[np.ndarray], weights: Sequence[float]
                ) -> np.ndarray:
    """One leaf's dataset-size weighted mean over the clients, float64."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    return sum(wi * np.asarray(x, np.float64) for wi, x in zip(w, xs))


def _half_step_bound(sent: np.ndarray, scale: np.ndarray) -> np.ndarray:
    s = np.asarray(sent, np.float32)
    return scale / 2.0 + 2.0 * np.spacing(np.abs(s)).astype(np.float64)


def _quantized(x: np.ndarray) -> bool:
    x = np.asarray(x)
    return x.dtype.kind == "f" and x.size > MIN_QUANT_SIZE


def _step(x: np.ndarray, b: Optional[np.ndarray], codec: str, qmax: int
          ) -> Tuple[np.ndarray, np.ndarray]:
    """(residual, quantization step per element), both float64, of one
    leaf under the codec's rule: ``int8`` one symmetric scale per leaf;
    ``delta`` one per ``BLOCK`` elements of the residual against the
    leaf's base (zero where it has none)."""
    xf = np.asarray(x, np.float32).reshape(-1)
    if codec == "int8":
        r = xf.astype(np.float64)
        return r, np.full(r.shape, np.max(np.abs(r)) / qmax or 1.0)
    bf = (np.zeros_like(xf) if b is None
          else np.asarray(b, np.float32).reshape(-1))
    r = (xf - bf).astype(np.float64)
    blocks = np.pad(np.abs(r), (0, (-r.size) % BLOCK)).reshape(-1, BLOCK)
    step = np.repeat(np.maximum(blocks.max(axis=1) / qmax, 1e-12),
                     BLOCK)[:r.size]
    return r, step


def codec_err(sent: Sequence[np.ndarray], restored: Sequence[np.ndarray],
              bases: Sequence[Optional[np.ndarray]], codec: str) -> float:
    """Worst restore error, in half int8 quantization steps, over every
    float leaf the codec quantizes."""
    out = 0.0
    for x, y, b in zip(sent, restored, bases):
        if not _quantized(x):
            continue
        _, step = _step(x, b, codec, 127)
        xf = np.asarray(x, np.float64).reshape(-1)
        err = np.abs(np.asarray(y, np.float64).reshape(-1) - xf) \
            / _half_step_bound(np.asarray(x).reshape(-1), step)
        out = max(out, float(np.max(err)))
    return out


def requantize(sent: Sequence[np.ndarray],
               bases: Sequence[Optional[np.ndarray]], codec: str,
               bits: int) -> List[np.ndarray]:
    """The codec's restore computed with ``bits``-bit codes: the
    control, one precision step below int8."""
    qmax = 2 ** (bits - 1) - 1
    out = []
    for x, b in zip(sent, bases):
        if not _quantized(x):
            out.append(x)
            continue
        r, step = _step(x, b, codec, qmax)
        q = np.clip(np.rint(r / step), -qmax, qmax)
        base = 0.0 if (codec == "int8" or b is None) else \
            np.asarray(b, np.float64).reshape(-1)
        out.append((q * step + base).reshape(np.shape(x)))
    return out


def pack(leaves: Sequence[Optional[np.ndarray]], like: Sequence[np.ndarray]
         ) -> np.ndarray:
    """Float leaves in one float32 buffer, each from a ``BLOCK``-aligned
    offset with zeros between: the codec's packed layout. A ``None``
    leaf packs as zeros shaped like its entry in ``like``."""
    sizes = [int(np.asarray(x).size) for x in like]
    starts = np.cumsum([0] + [-(-n // BLOCK) * BLOCK for n in sizes])
    flat = np.zeros(int(starts[-1]), np.float32)
    for lo, n, x in zip(starts, sizes, leaves):
        if x is not None:
            flat[lo:lo + n] = np.asarray(x, np.float32).reshape(-1)
    return flat


def quantize_blocks(flat: np.ndarray, base: Optional[np.ndarray],
                    qmax: int = 127) -> Tuple[np.ndarray, np.ndarray]:
    """Blockwise symmetric quantization of ``flat - base`` in float32:
    per ``BLOCK`` elements scale = max|r| / qmax, code = round(r /
    scale). Returns (codes (n,) int32, scales float32)."""
    r = flat if base is None else flat - base
    blocks = np.pad(r, (0, (-r.size) % BLOCK)).reshape(-1, BLOCK)
    scales = np.maximum(np.abs(blocks).max(axis=1) / np.float32(qmax),
                        np.float32(1e-12)).astype(np.float32)
    q = np.clip(np.rint(blocks / scales[:, None]), -qmax, qmax)
    return q.reshape(-1)[:r.size].astype(np.int32), scales


def kernel_numbers(leaves: Sequence[np.ndarray],
                   bases: Optional[Sequence[Optional[np.ndarray]]],
                   codes: np.ndarray, scales: np.ndarray) -> Dict[str, int]:
    """The packed quantize's codes and scales, as the program produced
    them from ``leaves`` (against ``bases``), beside ``quantize_blocks``
    of the same inputs."""
    flat = pack(leaves, leaves)
    base = None if bases is None else pack(bases, leaves)
    q_ref, s_ref = quantize_blocks(flat, base)
    s = np.asarray(scales, np.float32)[:s_ref.size]
    ulps = np.abs(s.view(np.int32).astype(np.int64)
                  - s_ref.view(np.int32).astype(np.int64))
    diff = np.abs(np.asarray(codes)[:flat.size].astype(np.int32) - q_ref)
    r = (flat if base is None else flat - base).astype(np.float64)
    t = r / np.repeat(s_ref.astype(np.float64), BLOCK)[:flat.size]
    tie = np.abs(np.abs(t - np.floor(t)) - 0.5) <= TIE_TOL
    off = (diff > 1) | ((diff == 1) & ~tie)
    return {"kernel_code_off": int(off.sum()),
            "kernel_scale_ulps": int(ulps.max()) if ulps.size else 0}


def raw_mismatch(sent: Sequence[np.ndarray],
                 restored: Sequence[np.ndarray]) -> int:
    bad = 0
    for x, y in zip(sent, restored):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            bad += max(x.size, 1)
            continue
        xb = np.ascontiguousarray(x).reshape(-1).view(np.uint8)
        yb = np.ascontiguousarray(y).reshape(-1).view(np.uint8)
        bad += int((xb.reshape(x.size, -1) != yb.reshape(y.size, -1)
                    ).any(axis=-1).sum()) if x.size else 0
    return bad


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Any]:
    """Every number the limits name, beside its limit; correct when each
    is finite and within it. A limit with no number fails. A number the
    limits do not name is not compared (a cell's ``limits`` file says
    why)."""
    rows, ok = {}, True
    for name, limit in sorted(limits.items()):
        if name.startswith("_"):
            continue
        value = numbers.get(name, math.inf)
        good = math.isfinite(value) and value <= limit
        ok = ok and good
        rows[name] = {"value": value, "limit": limit}
    return {"correct": ok, "checks": rows}
