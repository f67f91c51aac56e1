"""Pallas TPU kernel for the WKV6 chunked recurrence.

TPU adaptation (DESIGN.md §8): the original CUDA kernel assigns one
thread per (head, channel); TPUs have no warps, so we re-block the
recurrence for the MXU/VPU instead:

  grid = (B·H, T/CHUNK), dimension 1 sequential ("arbitrary") — the
  matrix-valued state S (K, V) lives in a VMEM scratch buffer and carries
  across chunk iterations. Inside a chunk the token loop is a
  fori_loop of rank-1 state updates (outer products on the VPU), while
  the read-out y_t = r_t·(S + u⊙k_t v_tᵀ) uses MXU-aligned (K, V)
  operands. K = V = 64 (RWKV head size), so a (64, 64) fp32 state tile
  fits VMEM comfortably alongside the (CHUNK, 64) operand tiles.

Validated in interpret mode against ``ref.wkv6_ref``; ``interpret=None``
(the default) auto-detects the platform like ``fedavg_agg``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fedavg_agg.fedavg_agg import resolve_interpret


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_final_ref,
                 s_scratch, *, chunk: int, nchunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scratch[...] = jnp.zeros_like(s_scratch)

    u = u_ref[...].astype(jnp.float32)              # (K,)

    def tok(t, S):
        rt = r_ref[t, :].astype(jnp.float32)        # (K,)
        kt = k_ref[t, :].astype(jnp.float32)
        vt = v_ref[t, :].astype(jnp.float32)        # (V,)
        wt = w_ref[t, :].astype(jnp.float32)
        kv = kt[:, None] * vt[None, :]              # (K, V)
        y = (rt[:, None] * (S + u[:, None] * kv)).sum(axis=0)   # (V,)
        y_ref[t, :] = y.astype(y_ref.dtype)
        return S * wt[:, None] + kv

    S = jax.lax.fori_loop(0, chunk, tok, s_scratch[...])
    s_scratch[...] = S

    @pl.when(ci == nchunks - 1)
    def _final():
        s_final_ref[...] = S


def wkv6_chunked(r, k, v, w, u, *, chunk: int = 64,
                 interpret: Optional[bool] = None):
    """r/k/w: (B, T, H, K); v: (B, T, H, V); u: (H, K).
    Returns (y (B, T, H, V), final state (B, H, K, V))."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    nchunks = T // chunk

    # (B, T, H, D) -> (B*H, T, D) so the grid rows are independent heads
    def fold(x):
        return jnp.moveaxis(x, 2, 1).reshape(B * H, T, x.shape[-1])

    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(w)
    uf = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, K)

    kernel = functools.partial(_wkv6_kernel, chunk=chunk, nchunks=nchunks)
    y, s_final = pl.pallas_call(
        kernel,
        grid=(B * H, nchunks),
        in_specs=[
            pl.BlockSpec((None, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, V), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, K), lambda b, c: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, V), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, K, V), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, V), r.dtype),
            jax.ShapeDtypeStruct((B * H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(rf, kf, vf, wf, uf)
    return (y.reshape(B, H, T, V).swapaxes(1, 2),
            s_final.reshape(B, H, K, V))
