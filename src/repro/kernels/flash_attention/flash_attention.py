"""Pallas TPU flash attention (forward).

Grid: (B·H heads, S/BQ query blocks). Each program instance holds one
(BQ, hd) query tile in VMEM and loops over T/BK key/value tiles with the
online-softmax recurrence, so VMEM never sees an (S, T) logit matrix.
GQA (``rep`` query heads per KV head) repeats each KV head ``rep`` times
and folds the query heads into the grid's batch axis, so every row keeps
its own causal position (BQ, BK, hd multiples of 128 where the model
allows keep the MXU dims hardware-aligned).

Masking (causal / sliding window) is applied from block-relative
positions; fully-masked key blocks are skipped by clamping the kv loop
bound per query block (causal: kv blocks beyond the diagonal never run).

Validated in interpret mode against ``ref.attention_ref`` (CPU); the TPU
path is the same kernel compiled. ``interpret=None`` (the default)
auto-detects the platform like ``fedavg_agg.resolve_interpret``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fedavg_agg.fedavg_agg import resolve_interpret

BIG_NEG = -2.3819763e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int,
                  seq_t: int, causal: bool, window: int, softcap: float,
                  scale: float):
    qi = pl.program_id(1)                      # query block index
    q = q_ref[...].astype(jnp.float32) * scale  # (BQ, hd)
    hd = q.shape[-1]

    nkv = seq_t // bk
    if causal:
        # keys strictly after the last query of this block never attend
        nkv_live = jnp.minimum(nkv, (qi * bq + bq + bk - 1) // bk)
    else:
        nkv_live = nkv

    def body(kv_i, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(kv_i * bk, bk), :].astype(jnp.float32)  # (BK, hd)
        v = v_ref[pl.ds(kv_i * bk, bk), :].astype(jnp.float32)
        lg = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (BQ, BK)
        if softcap > 0:
            lg = softcap * jnp.tanh(lg / softcap)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        dist = qpos - kpos
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok = ok & (dist >= 0)
        if window > 0:
            ok = ok & (dist < window)
        lg = jnp.where(ok, lg, BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(lg, axis=-1))
        p = jnp.exp(lg - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), BIG_NEG, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nkv_live, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        softcap: float = 0.0, block_q: int = 128,
                        block_k: int = 128,
                        interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, S, hd); k/v: (B, KV, T, hd). Returns (B, H, S, hd).

    S must divide by block_q and T by block_k (pad upstream if needed).
    """
    B, H, S, hd = q.shape
    KV, T = k.shape[1], k.shape[2]
    rep = H // KV
    bq = min(block_q, S)
    bk = min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)

    # GQA: the causal mask needs per-row positions, so rep > 1 folds the
    # query heads into the batch axis and repeats each KV head rep times
    if rep > 1:
        qf = q.reshape(B * H, 1, S, hd)
        kf = jnp.repeat(k, rep, axis=1).reshape(B * H, 1, T, hd)
        vf = jnp.repeat(v, rep, axis=1).reshape(B * H, 1, T, hd)
        out = _call(qf, kf, vf, bq, bk, causal, window, softcap, hd,
                    interpret)
        return out.reshape(B, H, S, hd)
    out = _call(q.reshape(B * KV, 1, S, hd), k.reshape(B * KV, 1, T, hd),
                v.reshape(B * KV, 1, T, hd), bq, bk, causal, window,
                softcap, hd, interpret)
    return out.reshape(B, H, S, hd)


def _call(qf, kf, vf, bq, bk, causal, window, softcap, hd, interpret):
    BH, _, S, _ = qf.shape
    T = kf.shape[2]
    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, seq_t=T, causal=causal,
        window=int(window), softcap=float(softcap),
        scale=1.0 / (hd ** 0.5))
    return pl.pallas_call(
        kernel,
        grid=(BH, S // bq),
        in_specs=[
            pl.BlockSpec((None, None, bq, hd), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((None, None, T, hd), lambda b, i: (b, 0, 0, 0)),
            pl.BlockSpec((None, None, T, hd), lambda b, i: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, hd),
                               lambda b, i: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf).reshape(BH, 1, S, hd)
