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
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fedavg_agg.fedavg_agg import (has_compiled_pallas,
                                                 resolve_interpret)

__all__ = ["BLOCK", "ROWS", "quantize", "dequantize", "quantize_packed",
           "dequantize_packed", "has_compiled_pallas", "resolve_interpret"]

BLOCK = 1024
ROWS = 8


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


def _pad_rows(x: jax.Array) -> jax.Array:
    """(n,) -> (R_total, BLOCK) with R_total a ROWS multiple."""
    pad = (-x.shape[0]) % (ROWS * BLOCK)
    return jnp.pad(x, (0, pad)).reshape(-1, BLOCK)


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
    xp = _pad_rows(x)
    rt = xp.shape[0]
    specs = [pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0))]
    args = [xp]
    kernel = _quant_kernel
    if base is not None:
        specs.append(pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)))
        args.append(_pad_rows(base))
        kernel = _quant_res_kernel
    q, s = pl.pallas_call(
        kernel,
        grid=(rt // ROWS,),
        in_specs=specs,
        out_specs=[pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((ROWS, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rt, BLOCK), jnp.int8),
                   jax.ShapeDtypeStruct((rt, 1), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(*args)
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
    qp = _pad_rows(q)
    rt = qp.shape[0]
    scales = jnp.pad(scales.astype(jnp.float32),
                     (0, rt - scales.shape[0]),
                     constant_values=1.0).reshape(rt, 1)
    specs = [pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
             pl.BlockSpec((ROWS, 1), lambda i: (i, 0))]
    args = [qp, scales]
    kernel = _dequant_kernel
    if base is not None:
        pad = rt * BLOCK - base.shape[0]
        specs.append(pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)))
        args.append(jnp.pad(base, (0, pad)).reshape(-1, BLOCK))
        kernel = _dequant_res_kernel
    x = pl.pallas_call(
        kernel,
        grid=(rt // ROWS,),
        in_specs=specs,
        out_specs=pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rt, BLOCK), dtype),
        interpret=resolve_interpret(interpret),
    )(*args)
    return x.reshape(-1)[:n]
