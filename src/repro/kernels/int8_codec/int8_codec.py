"""Pallas TPU kernels: blockwise int8 quantize/dequantize.

FedFly ships server-stage checkpoints between edge servers; the int8
codec shrinks the payload ~4x (the beyond-paper overhead optimization).
On TPU the quantize pass is bandwidth-bound: each grid step loads one
(ROWS, BLOCK) fp tile into VMEM, computes row maxes on the VPU, scales,
rounds, and writes int8 — a single HBM pass. Dequantize is the inverse.

Two entry levels share the kernels:

  ``quantize``/``dequantize``              — one flat buffer (one leaf).
  ``quantize_packed``/``dequantize_packed`` — the *migration payload*
        path: the caller concatenates every float leaf of a checkpoint
        into one flat buffer (see ``ops.quantize_leaves``) and the whole
        multi-leaf payload quantizes in a SINGLE Pallas dispatch, instead
        of one dispatch (and one grid setup, one padding, one device
        roundtrip) per leaf. A ``base`` buffer switches the kernel to
        residual mode: it quantizes ``x - base`` — the delta codec used
        when the destination edge already holds a synced base version.

``interpret=None`` (the default) auto-detects like ``fedavg_agg``:
compiled Pallas on TPU/GPU, interpreter elsewhere — call sites never
silently pay the python-loop interpreter per leaf on hardware that can
compile the kernel. (The tree-level ops layer goes one step further and
routes CPU to a pure-numpy reference.)

Grid: (ceil(n / (ROWS·BLOCK)),); tiles are (ROWS, BLOCK) with BLOCK=1024
lanes (128-aligned) and ROWS=8 sublanes. Inside the kernels the scales
are a (ROWS, 1) column: Mosaic refuses a rank-1 (ROWS,) block, whose
length must be a multiple of 128 or the whole array. The wrappers
reshape at the boundary, so callers (and the FFLY wire format) still
see ceil(n/BLOCK) flat scales.

Each packed program is built once per padded length (``padded_len``:
n rounded up to whole TILEs), plain or residual, and is then called
from JAX's in-memory jit cache: the ``pallas_call`` alone sits behind a
module-level ``jax.jit`` whose index maps are module-level functions.
The padded length, not ``n``, keys the cache, so every payload in one
TILE bucket reuses one program. The layout to the (rows, BLOCK) grid
happens before the jit, so the kernel reads and writes HBM as an eager
call did: on the host for numpy buffers (``ops.quantize_leaves`` and
``dequantize_leaves`` allocate theirs at ``padded_len`` and trim what
comes back), by eager ops for device buffers. ``quantize_tiled`` /
``dequantize_tiled`` return the kernels' grids; ``quantize_packed`` /
``dequantize_packed`` flatten them. ``codec.kernel_builds`` counts the
traces.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.fedavg_agg.fedavg_agg import (has_compiled_pallas,
                                                 resolve_interpret)
from repro.obs import telemetry as obs

__all__ = ["BLOCK", "ROWS", "TILE", "padded_len", "quantize", "dequantize",
           "quantize_packed", "dequantize_packed", "quantize_tiled",
           "dequantize_tiled", "has_compiled_pallas", "resolve_interpret"]

BLOCK = 1024
ROWS = 8
TILE = ROWS * BLOCK          # elements of one grid step's (ROWS, BLOCK) tile


def _quantize_tile(x, q_ref, s_ref):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0,
                        1e-12)                           # (ROWS, 1)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _quant_kernel(x_ref, q_ref, s_ref):
    _quantize_tile(x_ref[...].astype(jnp.float32), q_ref, s_ref)


def _quant_res_kernel(x_ref, b_ref, q_ref, s_ref):
    """Residual mode: quantize x - base in the same VMEM pass."""
    _quantize_tile(x_ref[...].astype(jnp.float32)
                   - b_ref[...].astype(jnp.float32), q_ref, s_ref)


def _dequant_kernel(q_ref, s_ref, x_ref):
    q = q_ref[...].astype(jnp.float32)
    x_ref[...] = (q * s_ref[...]).astype(x_ref.dtype)


def _dequant_res_kernel(q_ref, s_ref, b_ref, x_ref):
    q = q_ref[...].astype(jnp.float32)
    x_ref[...] = (q * s_ref[...]
                  + b_ref[...].astype(jnp.float32)).astype(x_ref.dtype)


def padded_len(n: int) -> int:
    """Elements of a length-``n`` payload padded to whole (ROWS, BLOCK)
    tiles: the length each packed program is built for. A host buffer
    allocated at this length is handed to the kernel without a copy."""
    return -(-n // TILE) * TILE


def _grid(x, rows: int, cols: int, fill=0):
    """Lay a flat buffer out as the (rows, cols) grid a kernel reads, its
    tail padded with ``fill``. A numpy buffer is laid out on the host (a
    free reshape where it already has ``rows * cols`` elements), a device
    buffer by eager ops; either way before the jitted core, so the
    kernel reads its operands from HBM as they arrive."""
    xp = np if isinstance(x, np.ndarray) else jnp
    if x.shape[0] != rows * cols:
        x = xp.pad(x, (0, rows * cols - x.shape[0]), constant_values=fill)
    return x.reshape(rows, cols)


def _tile_index(i):
    return (i, 0)


_TILE_SPEC = pl.BlockSpec((ROWS, BLOCK), _tile_index)
_SCALE_SPEC = pl.BlockSpec((ROWS, 1), _tile_index)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _quantize_tiles(x: jax.Array, base: Optional[jax.Array], *,
                    interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """x, base: (rt, BLOCK) -> (q (rt, BLOCK) int8, scales (rt, 1) f32).
    The program is the kernel alone. A ``None`` base (plain) and a buffer
    (residual) are different argument structures, so each keys a program
    of its own."""
    obs.count("codec.kernel_builds")         # runs only while tracing
    args = [x] if base is None else [x, base]
    rt = x.shape[0]
    return pl.pallas_call(
        _quant_kernel if base is None else _quant_res_kernel,
        grid=(rt // ROWS,),
        in_specs=[_TILE_SPEC] * len(args),
        out_specs=[_TILE_SPEC, _SCALE_SPEC],
        out_shape=[jax.ShapeDtypeStruct((rt, BLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((rt, 1), jnp.float32)],
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _dequantize_tiles(q: jax.Array, scales: jax.Array,
                      base: Optional[jax.Array], *, dtype,
                      interpret: bool) -> jax.Array:
    """q, base: (rt, BLOCK); scales: (rt, 1) -> x (rt, BLOCK)."""
    obs.count("codec.kernel_builds")         # runs only while tracing
    args = [q, scales] if base is None else [q, scales, base]
    return pl.pallas_call(
        _dequant_kernel if base is None else _dequant_res_kernel,
        grid=(q.shape[0] // ROWS,),
        in_specs=[_TILE_SPEC, _SCALE_SPEC, _TILE_SPEC][:len(args)],
        out_specs=_TILE_SPEC,
        out_shape=jax.ShapeDtypeStruct(q.shape, dtype),
        interpret=interpret,
    )(*args)


def quantize_tiled(x, base=None, *, interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """x, base: (n,) float, numpy or device, n > 0 -> the kernel's grids
    (q (rt, BLOCK) int8, scales (rt, 1) f32), rt·BLOCK = padded_len(n)."""
    rt = padded_len(x.shape[0]) // BLOCK
    return _quantize_tiles(
        _grid(x, rt, BLOCK), None if base is None else _grid(base, rt, BLOCK),
        interpret=resolve_interpret(interpret))


def dequantize_tiled(q, scales, base=None, dtype=jnp.float32, *,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Inverse of ``quantize_tiled``: q (m,) int8, scales, base, numpy or
    device, m > 0, trimmed or not -> x (rt, BLOCK), rt·BLOCK =
    padded_len(m). Missing scales are padded with 1."""
    rt = padded_len(q.shape[0]) // BLOCK
    return _dequantize_tiles(
        _grid(q, rt, BLOCK), _grid(scales.astype(np.float32), rt, 1, 1.0),
        None if base is None else _grid(base, rt, BLOCK),
        dtype=jnp.dtype(dtype), interpret=resolve_interpret(interpret))


def quantize(x: jax.Array, *, interpret: Optional[bool] = None):
    """x: (n,) float -> (q (n_pad,) int8, scales (n_pad/BLOCK,) f32)."""
    return quantize_packed(x, interpret=interpret)


def quantize_packed(x: jax.Array, base: Optional[jax.Array] = None, *,
                    interpret: Optional[bool] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """One dispatch over a (multi-leaf) flat buffer; residual vs ``base``
    when given. x, base: (n,) float -> (q (n_pad,) int8, scales f32)."""
    if x.shape[0] == 0:
        return (jnp.zeros((0,), jnp.int8), jnp.zeros((0,), jnp.float32))
    q, s = quantize_tiled(x, base, interpret=interpret)
    return q.reshape(-1), s.reshape(-1)


def dequantize(q: jax.Array, scales: jax.Array, n: int, dtype=jnp.float32,
               *, interpret: Optional[bool] = None):
    return dequantize_packed(q, scales, n, dtype=dtype, interpret=interpret)


def dequantize_packed(q: jax.Array, scales: jax.Array, n: int,
                      base: Optional[jax.Array] = None, dtype=jnp.float32,
                      *, interpret: Optional[bool] = None):
    """Inverse of ``quantize_packed``; adds ``base`` back in-kernel when
    decoding a residual payload. Accepts a trimmed ``q``/``scales`` (the
    serialized container stores only n q-bytes and ceil(n/BLOCK) scales)
    and re-pads to the kernel tile."""
    if n == 0:
        return jnp.zeros((0,), dtype)
    x = dequantize_tiled(q, scales, base, dtype, interpret=interpret)
    return x.reshape(-1)[:n]
