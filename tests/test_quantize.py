"""Quantize/dequantize/pack invariants (unit + hypothesis property tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # optional-dep shim

from repro.core import lut, quantize, scaling


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 8), st.integers(1, 16),
       st.sampled_from(["nf4", "nf2", "int8", "nf3", "fp4"]),
       st.integers(0, 2**31 - 1))
def test_pack_unpack_roundtrip(rows, groups, name, seed):
    ps = quantize.pack_spec(name)
    cols = groups * ps.group_codes  # cross-byte: nf3 = 8 codes / 3 bytes
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, len(lut.codebook(name)),
                         (rows, cols)).astype(np.uint8)
    packed = quantize.pack_codes(jnp.asarray(codes), name)
    assert packed.shape == (rows, groups * ps.group_bytes)
    out = quantize.unpack_codes(packed, name)
    np.testing.assert_array_equal(codes, np.asarray(out))


def test_pack_spec_layout():
    """Storage contract: true bit-packing densities, little-endian groups."""
    assert quantize.pack_spec("nf4").packed_width(256) == 128
    assert quantize.pack_spec("nf3").packed_width(256) == 96  # 3 bits/code
    assert quantize.pack_spec("nf2").packed_width(256) == 64
    assert quantize.pack_spec("int8").packed_width(256) == 256
    # slot-major planes: a row of K codes is g planes of W = K/g codes;
    # group j holds code i*W + j in slot i (bits [bits*i, bits*(i+1)))
    codes = jnp.asarray([[1, 2, 3, 0]], jnp.uint8)
    assert np.asarray(quantize.pack_codes(codes, "nf4")).tolist() \
        == [[1 | (3 << 4), 2 | (0 << 4)]]
    # one group per row (W = 1): the plain little-endian group
    assert np.asarray(quantize.pack_codes(codes, "nf2")).tolist() \
        == [[1 | (2 << 2) | (3 << 4)]]
    # nf3 group: 8 codes -> one little-endian 24-bit word -> 3 bytes
    codes = jnp.asarray([[5, 1, 7, 2, 0, 3, 6, 4]], jnp.uint8)
    word = sum(c << (3 * i) for i, c in enumerate([5, 1, 7, 2, 0, 3, 6, 4]))
    assert np.asarray(quantize.pack_codes(codes, "nf3")).tolist() \
        == [[word & 0xFF, (word >> 8) & 0xFF, (word >> 16) & 0xFF]]
    # nf3 with W = 2 groups: byte c of group j sits at column c*W + j, so
    # each byte plane is contiguous
    row = [5, 1, 7, 2, 0, 3, 6, 4, 1, 1, 2, 3, 5, 7, 0, 6]
    words = [sum(row[i * 2 + j] << (3 * i) for i in range(8))
             for j in range(2)]
    want = [(words[j] >> (8 * c)) & 0xFF for c in range(3) for j in range(2)]
    got = quantize.pack_codes(jnp.asarray([row], jnp.uint8), "nf3")
    assert np.asarray(got).tolist() == [want]
    # K padding re-packs: the planes of a wider row are re-laid out
    wide = quantize.repack_width(got, 32, "nf3")
    np.testing.assert_array_equal(
        np.asarray(quantize.unpack_codes(wide, "nf3")),
        np.asarray([row + [0] * 16], np.uint8))


def test_pack_errors_are_descriptive():
    with pytest.raises(ValueError, match="pack_spec"):
        quantize.codes_per_byte("nf3")  # cross-byte: no integer codes/byte
    with pytest.raises(ValueError, match="unknown codebook"):
        quantize.pack_spec("nf5")
    with pytest.raises(ValueError, match="divisible"):
        quantize.pack_spec("nf3").packed_width(12)  # 12 % 8 != 0


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from(["nf4", "nf2", "int4"]))
def test_blockwise_error_bounded_by_half_gap(seed, name):
    """|w - dequant(quant(w))| <= scale * max_half_gap, elementwise."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32))
    q, s_blk = quantize.quantize_blockwise(w, 32, name)
    w_hat = quantize.dequantize_blockwise(q, s_blk, 32, name)
    cb = np.asarray(lut.codebook(name))
    half_gap = np.max(np.diff(cb)) / 2
    bound = np.repeat(np.asarray(s_blk), 32, axis=1) * half_gap + 1e-6
    assert np.all(np.abs(np.asarray(w - w_hat)) <= bound)


def test_blockwise_idempotent():
    """Quantizing an already-dequantized weight is a fixed point."""
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 128)) * 0.1
    q1, s1 = quantize.quantize_blockwise(w, 64, "nf4")
    w1 = quantize.dequantize_blockwise(q1, s1, 64, "nf4")
    q2, s2 = quantize.quantize_blockwise(w1, 64, "nf4")
    w2 = quantize.dequantize_blockwise(q2, s2, 64, "nf4")
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-6)


def test_quantize_codes_negative_scale_argmin():
    """Alg.1 quantization step must be exact for negative scales too."""
    w = jnp.asarray([[0.5, -0.5, 0.2]], jnp.float32)
    s = jnp.asarray([[-1.0, -1.0, -0.5]], jnp.float32)
    codes = quantize.quantize_codes(w, s, "nf4")
    cb = np.asarray(lut.codebook("nf4"))
    picked = cb[np.asarray(codes, np.int32)[0]]
    for j in range(3):
        errs = (float(s[0, j]) * cb - float(w[0, j])) ** 2
        assert np.isclose((float(s[0, j]) * picked[j] - float(w[0, j])) ** 2,
                          errs.min(), atol=1e-10)


def test_fake_quant_matches_two_step():
    w = jax.random.normal(jax.random.PRNGKey(2), (8, 64)) * 0.05
    b, a = scaling.lords_init_from_weight(w, 32, rank=2)
    s = scaling.scale_matrix(b, a)
    fq = quantize.fake_quant(w, s, "nf4")
    codes = quantize.quantize_codes(w, s, "nf4")
    two = quantize.dequantize_codes(codes, s, "nf4", dtype=w.dtype)
    np.testing.assert_allclose(np.asarray(fq), np.asarray(two), atol=1e-7)


@pytest.mark.parametrize("m,bs", [(16, 32), (64, 128), (128, 128)])
def test_eff_block_clamps(m, bs):
    w = jax.random.normal(jax.random.PRNGKey(3), (4, m))
    s_blk = scaling.blockwise_scales(w, bs)
    assert s_blk.shape == (4, m // min(bs, m))
