"""Tree/multi-leaf helpers for migration payloads.

``quantize_leaves`` is the migration hot path: it concatenates every
float leaf of a checkpoint into ONE flat buffer (with an element offset
table) and quantizes the whole payload in a single dispatch — one
Pallas launch on TPU/GPU, one vectorized numpy pass on CPU — instead of
one dispatch per leaf. Pass ``base_leaves`` (aligned list, ``None``
entries allowed) to quantize residuals ``x - base`` for the delta codec;
leaves without a base are quantized against an implicit zero base,
which is exactly blockwise int8 of the value.

Backend selection mirrors ``fedavg_agg``: ``use_pallas``/``interpret``
default to ``None`` = auto-detect — compiled Pallas on TPU/GPU, the
pure-numpy reference on CPU (never the interpreter's python grid loop
on the production path).

On the Pallas path the host pads: the payload and its base are
scattered into numpy buffers of ``int8_codec.padded_len(n)`` elements,
which the codec lays out as its kernels' grid without a copy, and codes,
scales and decoded values come back as grids trimmed on the host. The
packed programs are built once per padded length and reused by every
later payload of that length.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.kernels.int8_codec.int8_codec import (BLOCK, dequantize_packed,
                                                 dequantize_tiled,
                                                 has_compiled_pallas,
                                                 padded_len, quantize_packed,
                                                 quantize_tiled)
from repro.kernels.int8_codec.ref import (dequantize_packed_ref,
                                          dequantize_ref,
                                          quantize_packed_ref, quantize_ref)


def _resolve_use_pallas(use_pallas: Optional[bool]) -> bool:
    return has_compiled_pallas() if use_pallas is None else use_pallas


def num_scales(n: int, block: int = BLOCK) -> int:
    return -(-n // block)


def _aligned(n: int, block: int = BLOCK) -> int:
    return -(-n // block) * block


def leaf_offsets(leaves: Sequence[np.ndarray]) -> np.ndarray:
    """BLOCK-aligned start offsets ((len+1,) int64) of each leaf in the
    packed flat buffer — computable from sizes alone, without
    materializing the buffer (header/size planning)."""
    starts = np.zeros(len(leaves) + 1, np.int64)
    for i, x in enumerate(leaves):
        starts[i + 1] = starts[i] + _aligned(int(np.asarray(x).size))
    return starts


def _scatter(flat: np.ndarray, leaves, offsets: np.ndarray) -> np.ndarray:
    """Write each leaf into ``flat`` at its offset; a ``None`` leaf
    leaves its span zero."""
    for i, x in enumerate(leaves):
        if x is not None:
            arr = np.asarray(x, np.float32).reshape(-1)
            flat[offsets[i]:offsets[i] + arr.size] = arr
    return flat


def pack_leaves(leaves: Sequence[np.ndarray]
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Concatenate float leaves into one f32 flat buffer; returns
    (flat (n_pad,), offsets (len+1,) int64). Each leaf starts at a
    BLOCK-aligned offset (zero padding in between), so quantization
    blocks never straddle two leaves: every leaf's error bound stays a
    function of its OWN dynamic range, and a leaf decodes from
    ``flat[offsets[i] : offsets[i] + size_i]`` independently. The
    padding costs < BLOCK elements per leaf — noise against multi-MB
    checkpoint payloads."""
    starts = leaf_offsets(leaves)
    return _scatter(np.zeros(int(starts[-1]), np.float32), leaves,
                    starts), starts


def quantize_leaves(leaves: Sequence[np.ndarray],
                    base_leaves: Optional[Sequence[Optional[np.ndarray]]]
                    = None, *,
                    use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """All float leaves -> ONE quantize dispatch.

    Returns (q (n,) int8, scales (ceil(n/BLOCK),) f32, offsets). With
    ``base_leaves``, residuals are quantized; a ``None`` base entry means
    a zero base for that leaf (plain blockwise int8).
    """
    pallas = _resolve_use_pallas(use_pallas)
    offsets = leaf_offsets(leaves)
    n = int(offsets[-1])
    size = padded_len(n) if pallas else n
    flat = _scatter(np.zeros(size, np.float32), leaves, offsets)
    base_flat = (None if base_leaves is None else
                 _scatter(np.zeros_like(flat), base_leaves, offsets))
    if n == 0:
        return (np.zeros((0,), np.int8), np.zeros((0,), np.float32),
                offsets)
    if pallas:
        q, s = quantize_tiled(flat, base_flat, interpret=interpret)
        return (np.asarray(q).reshape(-1)[:n],
                np.asarray(s).reshape(-1)[:num_scales(n)], offsets)
    q, s = quantize_packed_ref(flat, base_flat)
    return q, s, offsets


def dequantize_leaves(q: np.ndarray, scales: np.ndarray,
                      offsets: np.ndarray,
                      shapes: Sequence[Tuple[int, ...]],
                      dtypes: Sequence[np.dtype],
                      base_leaves: Optional[Sequence[Optional[np.ndarray]]]
                      = None, *,
                      use_pallas: Optional[bool] = None,
                      interpret: Optional[bool] = None) -> List[np.ndarray]:
    """Inverse of ``quantize_leaves``: one dispatch, then slice per leaf
    by the offset table and cast to each leaf's dtype."""
    n = int(offsets[-1])
    pallas = _resolve_use_pallas(use_pallas)
    size = padded_len(n) if pallas else n
    base_flat = (None if base_leaves is None else
                 _scatter(np.zeros(size, np.float32), base_leaves, offsets))
    if n == 0:
        flat = np.zeros((0,), np.float32)
    elif pallas:
        flat = np.asarray(dequantize_tiled(
            q[:n], scales, base_flat, interpret=interpret)).reshape(-1)[:n]
    else:
        flat = dequantize_packed_ref(q, scales, n, base_flat)
    out = []
    for i, (shp, dt) in enumerate(zip(shapes, dtypes)):
        size = int(np.prod(shp)) if shp else 1
        out.append(flat[offsets[i]:offsets[i] + size]
                   .astype(dt, copy=False).reshape(shp))
    return out


def quantize_leaf(x, *, use_pallas: Optional[bool] = None,
                  interpret: Optional[bool] = None):
    flat = x.reshape(-1)
    if _resolve_use_pallas(use_pallas):
        return quantize_packed(flat, interpret=interpret)
    return quantize_ref(flat)


def roundtrip(x, *, use_pallas: Optional[bool] = None,
              interpret: Optional[bool] = None):
    """Quantize + dequantize one tensor (error-analysis helper)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    if _resolve_use_pallas(use_pallas):
        q, s = quantize_packed(flat, interpret=interpret)
        out = dequantize_packed(q, s, n, dtype=x.dtype, interpret=interpret)
    else:
        q, s = quantize_ref(flat)
        out = dequantize_ref(q, s, n, dtype=x.dtype)
    return out.reshape(x.shape)
