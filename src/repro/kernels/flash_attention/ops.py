"""Jit'd wrapper for the flash-attention kernel.

``flash_attention`` pads S/T to block multiples, dispatches to the Pallas
kernel (interpreted on CPU, compiled on TPU: ``interpret=None``
auto-detects), and is differentiable:
the backward pass recomputes attention via the pure-jnp oracle (standard
flash recompute strategy — O(S·BK) memory both ways).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128, interpret=None):
    """q: (B, H, S, hd); k/v: (B, KV, T, hd) -> (B, H, S, hd)."""
    S, T = q.shape[2], k.shape[2]
    qp, ps = _pad_to(q, block_q, 2)
    kp, pt = _pad_to(k, block_k, 2)
    vp, _ = _pad_to(v, block_k, 2)
    # padded keys sit at positions >= T; causal masking from real positions
    # excludes them for causal attention. For non-causal, padded keys must
    # be masked via a window trick — handled by the oracle path upstream.
    out = flash_attention_fwd(qp, kp, vp, causal=causal, window=window,
                              softcap=softcap, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    return out[:, :, :S]


def _fwd(q, k, v, causal, window, softcap, block_q, block_k, interpret):
    out = flash_attention(q, k, v, causal, window, softcap, block_q,
                          block_k, interpret)
    return out, (q, k, v)


def _bwd(causal, window, softcap, block_q, block_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=causal,
                                         window=window, softcap=softcap),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)
