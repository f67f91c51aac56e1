"""Checkpoint + serialization: bit-exact raw roundtrips for arbitrary
pytrees (hypothesis), bounded int8 error, EdgeCheckpoint metadata, and
the pickle-free versioned format guards."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.checkpoint import EdgeCheckpoint
from repro.runtime import serialization as ser

# property tests need hypothesis (requirements-dev.txt); the plain tests
# below run everywhere
try:
    import hypothesis.extra.numpy as hnp
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False


def _assert_tree_equal(a, b):
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


if HAS_HYPOTHESIS:
    dtypes = st.sampled_from([np.float32, np.float16, np.int32, np.int8,
                              np.int64])
    arrays = st.builds(
        lambda shape, dt, seed: np.random.default_rng(seed)
        .standard_normal(shape).astype(dt) if np.issubdtype(dt, np.floating)
        else np.random.default_rng(seed).integers(-100, 100,
                                                  shape).astype(dt),
        hnp.array_shapes(min_dims=0, max_dims=3, max_side=8), dtypes,
        st.integers(0, 2**31))

    @st.composite
    def pytrees(draw, depth=2):
        if depth == 0:
            return draw(arrays)
        return draw(st.one_of(
            arrays,
            st.lists(pytrees(depth=depth - 1), min_size=1, max_size=3),
            st.dictionaries(st.text("abcdef", min_size=1, max_size=4),
                            pytrees(depth=depth - 1), min_size=1,
                            max_size=3)))

    @settings(max_examples=40, deadline=None)
    @given(tree=pytrees())
    def test_raw_roundtrip_bit_exact(tree):
        data = ser.pack_pytree(tree, codec="raw")
        back = ser.unpack_pytree(data)
        _assert_tree_equal(tree, back)

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_int8_bounded_error(seed):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(256,)).astype(np.float32) * 5
        back = ser.unpack_pytree(ser.pack_pytree({"x": x},
                                                 codec="int8"))["x"]
        bound = np.abs(x).max() / 127.0 * 0.51 + 1e-6
        assert np.max(np.abs(back - x)) <= bound

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 1000), drift=st.floats(1e-4, 0.1))
    def test_delta_restore_within_quant_bound(seed, drift):
        """Property: delta restore equals the full tree within the int8
        quantization bound of the RESIDUAL dynamic range."""
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(700,)).astype(np.float32) * 3
        b = x + rng.normal(size=(700,)).astype(np.float32) * drift
        tree, base = {"w": x}, {"w": b}
        back = ser.unpack_pytree(
            ser.pack_pytree(tree, "delta", base=base, base_version="t"),
            base=base)["w"]
        # the residual x - b and the restore b + residual are both
        # rounded in float32 at |x| up to ~12, where one rounding step
        # (~1e-6) dwarfs the quantization error of a small drift
        rounding = 2 * np.spacing(np.abs(x).max())
        bound = np.abs(x - b).max() / 127.0 * 0.51 + 1e-7 + rounding
        assert np.max(np.abs(back - x)) <= bound


def test_raw_roundtrip_fixed():
    """Non-hypothesis spot check of the raw codec."""
    tree = {"a": np.arange(12, dtype=np.int32).reshape(3, 4),
            "b": [np.float16(1.5) * np.ones((2,), np.float16),
                  {"c": np.random.default_rng(0).normal(size=(5,))
                   .astype(np.float32)}]}
    _assert_tree_equal(tree, ser.unpack_pytree(ser.pack_pytree(tree)))


def test_bf16_roundtrip():
    import ml_dtypes
    x = np.arange(16, dtype=np.float32).astype(ml_dtypes.bfloat16)
    back = ser.unpack_pytree(ser.pack_pytree({"x": x}))
    assert back["x"].dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(back["x"], x)


def test_int8_smaller_payload():
    x = {"w": np.random.default_rng(0).normal(size=(128, 128))
         .astype(np.float32)}
    raw = ser.packed_size(x, "raw")
    q = ser.packed_size(x, "int8")
    assert q < raw / 3


def test_bad_magic_rejected():
    with pytest.raises(AssertionError):
        ser.unpack_pytree(b"NOPE" + b"\0" * 32)


# -- dtype / shape coverage of the codecs (satellite: untested paths) --------

def _dtype_cases():
    import ml_dtypes
    rng = np.random.default_rng(5)
    big = rng.normal(size=(300,))
    return [
        np.array(2.5, np.float32),                   # 0-d scalar
        np.array(1.5, ml_dtypes.bfloat16),           # 0-d bf16
        np.zeros((0,), np.float32),                  # empty
        np.zeros((3, 0, 2), ml_dtypes.bfloat16),     # empty multi-dim bf16
        big.astype(np.float16),
        big.astype(np.float32),
        big.astype(np.float64),
        big.astype(ml_dtypes.bfloat16),
        np.arange(200, dtype=np.int32),
        np.array(7, np.int64),                       # 0-d int
    ]


@pytest.mark.parametrize("codec", ["raw", "int8", "delta"])
def test_all_dtypes_roundtrip(codec):
    """Every leaf dtype/shape — including 0-d scalars, empty leaves and
    bfloat16 — must survive every codec with dtype+shape intact and
    error within the codec's bound (0 for raw and for quant-ineligible
    leaves)."""
    tree = {f"leaf{i}": x for i, x in enumerate(_dtype_cases())}
    back = ser.unpack_pytree(ser.pack_pytree(tree, codec=codec))
    for k, x in tree.items():
        y = back[k]
        assert y.dtype == x.dtype and y.shape == x.shape, k
        if not x.size:
            continue
        if (codec == "raw" or x.size <= 64
                or not np.issubdtype(
                    np.float32 if x.dtype.name == "bfloat16" else x.dtype,
                    np.floating)):
            np.testing.assert_array_equal(np.asarray(y, np.float64),
                                          np.asarray(x, np.float64), err_msg=k)
        else:
            bound = np.abs(np.asarray(x, np.float32)).max() / 127 * 0.51 \
                + (2e-2 if x.dtype.name == "bfloat16"
                   else 5e-3 if x.dtype.name == "float16" else 1e-6)
            assert np.abs(np.asarray(y, np.float32)
                          - np.asarray(x, np.float32)).max() <= bound, k


def test_v1_payloads_still_deserialize():
    """Backward compat: a v1 container (raw/int8 per-leaf encoding, no
    packed section) must unpack under the v2 reader."""
    tree = {"w": np.random.default_rng(0).normal(size=(200,))
            .astype(np.float32),
            "i": np.arange(10, dtype=np.int32)}
    for codec in ("raw", "int8"):
        data = ser.pack_pytree(tree, codec)
        # a v2 raw/int8 container is structurally identical to v1 —
        # rewriting the version field reconstructs a v1 payload exactly
        v1 = data[:4] + (1).to_bytes(4, "little") + data[8:]
        back = ser.unpack_pytree(v1)
        assert back["w"].shape == (200,)
        np.testing.assert_array_equal(back["i"], tree["i"])
    with pytest.raises(AssertionError):
        ser.unpack_pytree(data[:4] + (99).to_bytes(4, "little") + data[8:])


def test_chunked_pack_identical_to_monolithic():
    rng = np.random.default_rng(1)
    tree = {"a": rng.normal(size=(500, 41)).astype(np.float32),
            "b": [np.arange(64, dtype=np.int64),
                  rng.normal(size=(3000,)).astype(np.float32)]}
    base = {"a": tree["a"] * 0.99}
    for codec, kw in (("raw", {}), ("int8", {}),
                      ("delta", dict(base=base, base_version="v3"))):
        mono = ser.pack_pytree(tree, codec, **kw)
        chunks = list(ser.pack_pytree_chunks(tree, codec, **kw))
        assert b"".join(chunks) == mono
        assert all(len(c) <= 1 << 20 for c in chunks)


def test_delta_fallback_on_lossy_residual():
    """A base so far from the value that the residual would quantize
    lossier than the value itself ships the full leaf bit-exact."""
    x = np.random.default_rng(2).normal(size=(500,)).astype(np.float32)
    near = {"x": x + 1e-3}
    far = {"x": x + 50.0}
    d_near = ser.pack_pytree({"x": x}, "delta", base=near, base_version="n")
    d_far = ser.pack_pytree({"x": x}, "delta", base=far, base_version="f")
    assert len(d_far) > len(d_near)            # raw leaf > packed int8
    back = ser.unpack_pytree(d_far, base=far)["x"]
    np.testing.assert_array_equal(back, x)     # bit-exact fallback


def test_delta_without_base_is_blockwise_int8():
    x = np.random.default_rng(3).normal(size=(5000,)).astype(np.float32)
    data = ser.pack_pytree({"x": x}, "delta")
    assert ser.peek_base_version(data) is None
    back = ser.unpack_pytree(data)["x"]        # no base needed
    assert np.abs(back - x).max() <= np.abs(x).max() / 127 * 0.51 + 1e-7
    assert len(data) < x.nbytes / 3


def test_delta_requires_base_to_decode():
    x = np.random.default_rng(4).normal(size=(500,)).astype(np.float32)
    base = {"x": x * 0.999}
    data = ser.pack_pytree({"x": x}, "delta", base=base, base_version="v9")
    assert ser.peek_base_version(data) == "v9"
    with pytest.raises(ValueError, match="v9"):
        ser.unpack_pytree(data)
    # a base with the wrong structure is also rejected
    with pytest.raises(ValueError):
        ser.unpack_pytree(data, base={"y": x})


def test_delta_partial_base_mixed_leaves():
    """Leaves with a base ride as residuals, leaves without as zero-base
    int8, ints stay raw — all in one container."""
    rng = np.random.default_rng(6)
    tree = {"params": rng.normal(size=(900,)).astype(np.float32),
            "momentum": rng.normal(size=(900,)).astype(np.float32),
            "step": np.int64(12)}
    base = {"params": tree["params"] + 1e-3}
    data = ser.pack_pytree(tree, "delta", base=base, base_version="r1")
    back = ser.unpack_pytree(data, base=base)
    assert back["step"] == 12 and back["step"].dtype == np.int64
    assert np.abs(back["params"] - tree["params"]).max() <= \
        1e-3 / 127 * 0.51 * 2 + 1e-7           # residual-bounded (tight)
    assert np.abs(back["momentum"] - tree["momentum"]).max() <= \
        np.abs(tree["momentum"]).max() / 127 * 0.51 + 1e-7


def test_int_leaves_never_quantized():
    x = {"idx": np.arange(1000, dtype=np.int32)}
    back = ser.unpack_pytree(ser.pack_pytree(x, codec="int8"))
    np.testing.assert_array_equal(back["idx"], x["idx"])
    assert back["idx"].dtype == np.int32


def test_edge_checkpoint_roundtrip():
    params = {"layers": {"w": np.ones((4, 4), np.float32)}}
    opt = {"mu": {"layers": {"w": np.zeros((4, 4), np.float32)}},
           "step": np.int32(7)}
    ck = EdgeCheckpoint(client_id="pi3_1", round_idx=50, epoch=3,
                        batch_idx=11, split_point=2, server_params=params,
                        optimizer_state=opt, loss=1.25, rng_seed=42)
    back = EdgeCheckpoint.unpack(ck.pack())
    assert back.client_id == "pi3_1"
    assert (back.round_idx, back.epoch, back.batch_idx) == (50, 3, 11)
    assert back.split_point == 2
    assert back.loss == pytest.approx(1.25)
    _assert_tree_equal(back.server_params, params)
    _assert_tree_equal(back.optimizer_state, opt)


def test_checkpoint_contains_paper_fields():
    """Paper §IV: epoch number, gradients, model weights, loss value,
    optimizer state must all ride in the checkpoint."""
    grads = {"w": np.full((2, 2), 0.5, np.float32)}
    ck = EdgeCheckpoint(client_id="c", round_idx=1, epoch=2, batch_idx=3,
                        split_point=1, server_params={"w": np.ones((2, 2),
                                                                   np.float32)},
                        optimizer_state={"mu": grads}, last_grads=grads,
                        loss=0.5)
    back = EdgeCheckpoint.unpack(ck.pack())
    assert back.last_grads is not None
    np.testing.assert_array_equal(back.last_grads["w"], grads["w"])
