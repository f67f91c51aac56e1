"""Pallas kernel sweeps: shapes x dtypes, assert_allclose against the
pure-jnp oracles (interpret=True executes the kernel bodies on CPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fedavg_agg import (fedavg_agg, fedavg_agg_mix,
                                      fedavg_agg_mix_ref, fedavg_agg_ref,
                                      fedavg_mix_tree, has_compiled_pallas,
                                      resolve_interpret)
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.int8_codec import (dequantize, dequantize_packed,
                                      dequantize_packed_ref, dequantize_ref,
                                      quantize, quantize_packed,
                                      quantize_packed_ref, quantize_ref)
from repro.kernels.int8_codec.ops import (dequantize_leaves, pack_leaves,
                                          quantize_leaves, roundtrip)
from repro.kernels.wkv6 import wkv6, wkv6_ref
from repro.obs import telemetry as obs


# -- flash attention ---------------------------------------------------------

FLASH_CASES = [
    # B, H, KV, S, hd, causal, window, softcap, dtype
    (1, 4, 4, 128, 64, True, 0, 0.0, jnp.float32),
    (2, 8, 2, 256, 64, True, 0, 0.0, jnp.float32),
    (1, 4, 4, 128, 64, True, 32, 0.0, jnp.float32),
    (1, 4, 4, 128, 64, True, 0, 50.0, jnp.float32),
    (2, 2, 2, 96, 32, True, 0, 0.0, jnp.float32),       # padding path
    (1, 8, 8, 128, 128, True, 0, 0.0, jnp.bfloat16),
    (1, 2, 1, 64, 64, True, 16, 30.0, jnp.float32),     # GQA+win+cap
]


@pytest.mark.parametrize(
    "B,H,KV,S,hd,causal,win,cap,dt", FLASH_CASES,
    ids=[f"B{c[0]}H{c[1]}KV{c[2]}S{c[3]}hd{c[4]}w{c[6]}c{c[7]}{c[8].__name__}"
         for c in FLASH_CASES])
def test_flash_attention_matches_ref(B, H, KV, S, hd, causal, win, cap, dt):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dt)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dt)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dt)
    out = flash_attention(q, k, v, causal, win, cap, 64, 64, True)
    ref = attention_ref(q, k, v, causal=causal, window=win, softcap=cap)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_attention_grads_match_ref():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 64, 32))
    k = jax.random.normal(ks[1], (1, 2, 64, 32))
    v = jax.random.normal(ks[2], (1, 2, 64, 32))
    g1 = jax.grad(lambda a: flash_attention(a, k, v, True, 0, 0.0,
                                            64, 64, True).sum())(q)
    g2 = jax.grad(lambda a: attention_ref(a, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


# -- wkv6 --------------------------------------------------------------------

WKV_CASES = [(1, 64, 1, 64, 64), (2, 128, 2, 64, 64), (1, 96, 1, 64, 32),
             (2, 256, 4, 64, 128)]


@pytest.mark.parametrize("B,T,H,K,chunk", WKV_CASES,
                         ids=[f"B{c[0]}T{c[1]}H{c[2]}ch{c[4]}"
                              for c in WKV_CASES])
def test_wkv6_matches_ref(B, T, H, K, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (B, T, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, K)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, K))) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    y1, s1 = wkv6(r, k, v, w, u, chunk=chunk)
    y2, s2 = wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-5)


def test_wkv6_state_carries_across_chunks():
    """Final state after T tokens == running the ref in two halves."""
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    B, T, H, K = 1, 128, 1, 64
    r = jax.random.normal(ks[0], (B, T, H, K)) * 0.3
    k = jax.random.normal(ks[1], (B, T, H, K)) * 0.3
    v = jax.random.normal(ks[2], (B, T, H, K)) * 0.3
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, K))) * 0.4 + 0.5
    u = jax.random.normal(ks[4], (H, K)) * 0.1
    _, s_half = wkv6_ref(r[:, :64], k[:, :64], v[:, :64], w[:, :64], u)
    _, s_full_ref = wkv6_ref(r[:, 64:], k[:, 64:], v[:, 64:], w[:, 64:],
                             u, state0=s_half)
    _, s_kernel = wkv6(r, k, v, w, u, chunk=32)
    np.testing.assert_allclose(np.asarray(s_kernel),
                               np.asarray(s_full_ref), atol=2e-5)


# -- fedavg_agg ---------------------------------------------------------------

@pytest.mark.parametrize("E,n,dt", [(2, 4096, jnp.float32),
                                    (4, 10000, jnp.float32),
                                    (8, 4096, jnp.bfloat16),
                                    (3, 12288, jnp.float32)])
def test_fedavg_agg_sweep(E, n, dt):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (E, n), dt)
    w = jax.random.uniform(ks[1], (E,), jnp.float32, 0.1, 3.0)
    a = fedavg_agg(x, w)
    b = fedavg_agg_ref(x, w)
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=tol)


@pytest.mark.parametrize("E,n,dt", [(1, 4096, jnp.float32),
                                    (4, 10000, jnp.float32),
                                    (8, 4096, jnp.bfloat16),
                                    (13, 12288, jnp.float32)])
def test_fedavg_agg_mix_sweep(E, n, dt):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    g = jax.random.normal(ks[0], (n,), dt)
    x = jax.random.normal(ks[1], (E, n), dt)
    w = jax.random.uniform(ks[2], (E,), jnp.float32, 0.0, 0.5 / E)
    a = fedavg_agg_mix(g, x, w, interpret=True)
    b = fedavg_agg_mix_ref(g, x, w)
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=tol)


def test_fedavg_agg_mix_equals_sequential_mixing():
    """b_i = a_i * prod_{j>i}(1-a_j) makes one kernel call equal a chain
    of (1-a) g + a u mixes — the AsyncAggregator batching identity."""
    rng = np.random.default_rng(0)
    g = rng.normal(size=5000).astype(np.float32)
    x = rng.normal(size=(4, 5000)).astype(np.float32)
    alphas = [0.3, 0.12, 0.5, 0.08]
    seq = g.copy()
    for i, a in enumerate(alphas):
        seq = (1 - a) * seq + a * x[i]
    eff = [a * np.prod([1.0 - b for b in alphas[i + 1:]])
           for i, a in enumerate(alphas)]
    out = fedavg_agg_mix(jnp.asarray(g), jnp.asarray(x),
                         jnp.asarray(eff, jnp.float32), interpret=True)
    np.testing.assert_allclose(np.asarray(out), seq, atol=1e-5)


def test_fedavg_mix_tree_non_float_leaves_pass_through():
    g = {"w": np.ones((64, 64), np.float32), "step": np.array(7)}
    ups = [{"w": np.zeros((64, 64), np.float32), "step": np.array(9)}]
    out = fedavg_mix_tree(g, ups, [0.25])
    assert out["step"] == 7                      # ints never mixed
    np.testing.assert_allclose(out["w"], 0.75, atol=1e-6)


def test_interpret_autodetect_matches_backend():
    """interpret=None must resolve to the interpreter exactly when no
    compiled-Pallas platform is available (CPU)."""
    expected = jax.default_backend() not in ("tpu", "gpu")
    assert resolve_interpret(None) is expected
    assert has_compiled_pallas() is (not expected)
    assert resolve_interpret(True) is True and resolve_interpret(False) is False


# -- int8 codec ---------------------------------------------------------------

@pytest.mark.parametrize("n", [8192, 10000, 50000])
def test_int8_quantize_matches_ref(n):
    x = jax.random.normal(jax.random.PRNGKey(0), (n,)) * 4
    q1, s1 = quantize(x)
    q2, s2 = quantize_ref(x)
    np.testing.assert_array_equal(np.asarray(q1)[:len(np.asarray(q2))],
                                  np.asarray(q2))


@pytest.mark.parametrize("n,dt", [(8192, jnp.float32), (9000, jnp.float32),
                                  (8192, jnp.bfloat16)])
def test_int8_roundtrip_error_bound(n, dt):
    x = (jax.random.normal(jax.random.PRNGKey(1), (n,)) * 3).astype(dt)
    back = roundtrip(x)
    bound = float(jnp.max(jnp.abs(x.astype(jnp.float32)))) / 127 * 0.51 \
        + 2e-2
    assert float(jnp.max(jnp.abs(back.astype(jnp.float32)
                                 - x.astype(jnp.float32)))) <= bound


# -- packed / residual int8 ---------------------------------------------------

@pytest.mark.parametrize("n", [4096, 9000, 50000])
def test_quantize_packed_residual_matches_numpy_ref(n):
    """Pallas residual kernel (interpret) and the pure-numpy CPU
    production path must agree bit-for-bit on q."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(n,)).astype(np.float32) * 4
    base = x + rng.normal(size=(n,)).astype(np.float32) * 0.01
    q_ref, s_ref = quantize_packed_ref(x, base)
    q_k, s_k = quantize_packed(jnp.asarray(x), jnp.asarray(base),
                               interpret=True)
    np.testing.assert_array_equal(q_ref, np.asarray(q_k)[:n])
    np.testing.assert_allclose(s_ref, np.asarray(s_k)[:len(s_ref)],
                               rtol=1e-6)
    # and the residual roundtrip is bounded by the RESIDUAL range
    out = dequantize_packed(jnp.asarray(q_ref), jnp.asarray(s_ref), n,
                            jnp.asarray(base), interpret=True)
    bound = np.abs(x - base).max() / 127 * 0.51 + 1e-7
    assert float(jnp.max(jnp.abs(out - x))) <= bound
    out_np = dequantize_packed_ref(q_ref, s_ref, n, base)
    np.testing.assert_allclose(np.asarray(out), out_np, atol=1e-6)


PACKED_NS = [1, 1000, 8191, 8192, 8193, 70_001]


def _packed_inputs(n, residual):
    rng = np.random.default_rng(n)
    x = rng.normal(size=(n,)).astype(np.float32) * 4
    base = (x + rng.normal(size=(n,)).astype(np.float32) * 0.01
            if residual else None)
    return x, base


def _bits(a) -> np.ndarray:
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("residual", [False, True], ids=["plain", "residual"])
@pytest.mark.parametrize("n", PACKED_NS)
def test_packed_codec_matches_numpy_ref_and_eager(n, residual):
    """The jitted packed programs (interpret) give the numpy reference's
    codes, its scales within the one ulp the benchmark allows, and its
    decoded values within one ulp of the larger operand (q*s + base may
    round once); and every output bit-equal to the same kernels run
    eagerly, outside jit, as they ran before they were jitted."""
    x, base = _packed_inputs(n, residual)
    q_ref, s_ref = quantize_packed_ref(x, base)
    b = None if base is None else jnp.asarray(base)
    q, s = quantize_packed(jnp.asarray(x), b, interpret=True)
    out = dequantize_packed(jnp.asarray(q_ref), jnp.asarray(s_ref), n, b,
                            interpret=True)
    with jax.disable_jit():
        eager = (*quantize_packed(jnp.asarray(x), b, interpret=True),
                 dequantize_packed(jnp.asarray(q_ref), jnp.asarray(s_ref),
                                   n, b, interpret=True))
    for got, was in zip((q, s, out), eager):
        np.testing.assert_array_equal(_bits(got), _bits(was))
    np.testing.assert_array_equal(np.asarray(q)[:n], q_ref)
    ulps = np.abs(_bits(np.asarray(s)[:len(s_ref)]) - _bits(s_ref))
    assert int(ulps.max()) <= 1
    out_ref = dequantize_packed_ref(q_ref, s_ref, n, base)
    big = np.abs(out_ref) + (0 if base is None else np.abs(base))
    assert out.shape == (n,)
    assert np.all(np.abs(np.asarray(out) - out_ref) <= np.spacing(big))


def _kernel_builds() -> float:
    snap = obs.snapshot()
    return 0 if snap is None else snap["counters"].get("codec.kernel_builds",
                                                       0)


@pytest.mark.parametrize("residual", [False, True], ids=["plain", "residual"])
def test_packed_codec_built_once_per_padded_length(residual):
    """A second payload of the same padded length, or of another length
    in the same TILE bucket, reuses the programs the first one built."""
    from repro.kernels.int8_codec import int8_codec
    cores = (int8_codec._quantize_tiles, int8_codec._dequantize_tiles)
    for core in cores:
        core.clear_cache()
    tile = int8_codec.TILE
    obs.enable()
    try:
        for i, n in enumerate([3 * tile + 5, 3 * tile + 5, 4 * tile]):
            x, base = _packed_inputs(n, residual)
            b = None if base is None else jnp.asarray(base)
            q, s = quantize_packed(jnp.asarray(x), b, interpret=True)
            dequantize_packed(q, s, n, b, interpret=True).block_until_ready()
            assert _kernel_builds() == (2 if i == 0 else 0), n
    finally:
        obs.disable()
    assert [core._cache_size() for core in cores] == [1, 1]


def test_pack_leaves_block_aligned_offsets():
    """Leaves start on BLOCK boundaries so quantization blocks never
    straddle two leaves (a tiny leaf must not inherit a big neighbour's
    dynamic range)."""
    from repro.kernels.int8_codec.int8_codec import BLOCK
    leaves = [np.ones((130, 9), np.float32), np.ones((5,), np.float32),
              np.zeros((0,), np.float32), np.ones((2048,), np.float32)]
    flat, offsets = pack_leaves(leaves)
    assert all(int(o) % BLOCK == 0 for o in offsets)
    assert int(offsets[-1]) == flat.shape[0]
    # huge first leaf must not affect the small second leaf's scale
    leaves = [np.full((1000,), 1e4, np.float32),
              np.full((8,), 1e-3, np.float32)]
    q, s, off = quantize_leaves(leaves, use_pallas=False)
    outs = dequantize_leaves(q, s, off, [(1000,), (8,)],
                             [np.float32, np.float32], use_pallas=False)
    np.testing.assert_allclose(outs[1], leaves[1], rtol=0.01)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_quantize_leaves_multi_leaf_roundtrip(use_pallas):
    """One fused dispatch over many leaves == per-leaf error bounds."""
    rng = np.random.default_rng(0)
    leaves = [rng.normal(size=(65, 33)).astype(np.float32) * 3,
              rng.normal(size=(7,)).astype(np.float32),
              rng.normal(size=(2000,)).astype(np.float16),
              np.zeros((0,), np.float32)]
    bases = [leaves[0] * 0.999, None, None, None]
    kw = dict(use_pallas=use_pallas,
              interpret=True if use_pallas else None)
    q, s, off = quantize_leaves(leaves, bases, **kw)
    outs = dequantize_leaves(q, s, off, [x.shape for x in leaves],
                             [x.dtype for x in leaves], bases, **kw)
    for x, b, o in zip(leaves, bases, outs):
        assert o.shape == x.shape and o.dtype == x.dtype
        if not x.size:
            continue
        r = x.astype(np.float32) - (np.asarray(b, np.float32)
                                    if b is not None else 0.0)
        slop = 5e-3 if x.dtype == np.float16 else 1e-6
        err = np.abs(o.astype(np.float32) - x.astype(np.float32)).max()
        assert err <= np.abs(r).max() / 127 * 0.51 + slop
