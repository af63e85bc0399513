"""Quantize / dequantize primitives shared by block-wise and LoRDS paths.

Storage format
--------------
Codes are indices into a codebook (``repro.core.lut``).  On disk / in HBM we
pack them along the last axis in groups of ``g = PackSpec.group_codes``
codes per ``PackSpec.group_bytes`` bytes:

  * 8-bit codebooks (int8):          1 code  per byte   (1c/1B)
  * 4-bit codebooks (nf4/int4/fp4):  2 codes per byte   (2c/1B)
  * 3-bit codebooks (nf3):           8 codes per 3 bytes (8c/3B, cross-byte:
    the 8 codes form one 24-bit little-endian integer)
  * 2-bit codebooks (nf2/int2):      4 codes per byte   (4c/1B)

Groups are *slot-major planes*: a row of ``K`` codes is cut into ``g``
contiguous planes of ``W = K / g`` codes, and group ``j`` gathers column
``j`` of every plane — code slot ``i`` (bits ``[bits*i, bits*(i+1))`` of the
group's little-endian integer) holds logical code ``i*W + j``.  Byte ``c`` of
group ``j`` is stored at column ``c*W + j``, so every byte plane is
contiguous as well.  A kernel tile of packed columns therefore unpacks by
shift/mask alone into ``g`` lane-dense code planes, each a contiguous run of
logical K — no lane interleave.  Padding K changes ``W``, so a wider row is
re-packed, not byte-padded (:func:`repack_width`).  All functions are
jit-friendly and differentiable where meaningful.

:data:`PACK_LAYOUT` names this layout.  Everything that persists packed
codes records it (the streaming-PTQ plan fingerprint, checkpoint specs),
so codes written in another layout are refused rather than misread.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import lut
from repro.core.scaling import SCALE_EPS

__all__ = [
    "PACK_LAYOUT",
    "PackSpec",
    "pack_spec",
    "nearest_code",
    "quantize_codes",
    "dequantize_codes",
    "pack_codes",
    "unpack_codes",
    "repack_width",
    "packed_dim",
    "codes_per_byte",
    "fake_quant",
    "quantize_blockwise",
    "dequantize_blockwise",
]


# the stored byte layout of packed codes (module docstring); an artifact
# that records no layout was packed in an earlier one
PACK_LAYOUT = "planes"


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Bit-packing group layout: ``group_codes`` codes per ``group_bytes``
    bytes, little-endian (code i occupies bits [bits*i, bits*(i+1)) of the
    group's ``8 * group_bytes``-bit integer)."""

    bits: int
    group_codes: int
    group_bytes: int

    def packed_width(self, m: int) -> int:
        """Packed byte count for a logical last-axis width of ``m`` codes."""
        if m % self.group_codes:
            raise ValueError(
                f"last dim {m} not divisible by pack group {self.group_codes}"
                f" ({self.bits}-bit)")
        return m // self.group_codes * self.group_bytes

    def logical_width(self, mp: int) -> int:
        """Logical code count for a packed last-axis width of ``mp`` bytes."""
        if mp % self.group_bytes:
            raise ValueError(
                f"packed dim {mp} not divisible by group bytes "
                f"{self.group_bytes} ({self.bits}-bit)")
        return mp // self.group_bytes * self.group_codes


# bits -> (group_codes, group_bytes).  group_bytes==1 entries are
# byte-identical to the historical single-byte layout.
_PACK_SPECS = {
    8: PackSpec(8, 1, 1),
    4: PackSpec(4, 2, 1),
    3: PackSpec(3, 8, 3),
    2: PackSpec(2, 4, 1),
}


def pack_spec(codebook_name: str) -> PackSpec:
    """The storage :class:`PackSpec` for a codebook — the single source of
    the bits->pack-layout map."""
    bits = lut.codebook_bits(codebook_name)
    spec = _PACK_SPECS.get(bits)
    if spec is None:
        raise ValueError(
            f"no pack layout for {bits}-bit codebook {codebook_name!r}; "
            f"supported bit widths: {sorted(_PACK_SPECS)}")
    return spec


def nearest_code(x: jnp.ndarray, codebook_name: str) -> jnp.ndarray:
    """Index of the nearest codebook level for each element of ``x``.

    Implemented with ``searchsorted`` over the level midpoints — exact
    nearest-neighbour for a sorted 1-D codebook, O(log L) per element.
    """
    mids = lut.midpoints(codebook_name).astype(x.dtype)
    return jnp.searchsorted(mids, x, side="left").astype(jnp.uint8)


def quantize_codes(
    w: jnp.ndarray, s: jnp.ndarray, codebook_name: str
) -> jnp.ndarray:
    """Paper Alg. 1 quantization step: Q_ij = argmin_v (S_ij * v - W_ij)^2.

    For s != 0 this equals nearest-level rounding of w/s (the s^2 factor does
    not change the argmin); for s < 0 the division flips the ordering, which
    nearest-neighbour on w/s handles automatically.
    """
    safe = jnp.where(jnp.abs(s) < SCALE_EPS, SCALE_EPS, s)
    ratio = (w / safe).astype(jnp.float32)
    return nearest_code(ratio, codebook_name)


def dequantize_codes(
    codes: jnp.ndarray, s: jnp.ndarray, codebook_name: str, dtype=None
) -> jnp.ndarray:
    """W_hat = codebook[codes] * S."""
    levels = lut.codebook(codebook_name)
    vals = jnp.take(levels, codes.astype(jnp.int32), axis=0)
    out = vals * s
    return out.astype(dtype) if dtype is not None else out


def codes_per_byte(codebook_name: str) -> int:
    """Whole codes per uint8 for single-byte pack groups.

    Only defined when the pack group is one byte wide; 3-bit codes straddle
    byte boundaries (8 codes / 3 bytes) and must go through :func:`pack_spec`
    ``packed_width`` / ``logical_width`` instead.
    """
    spec = pack_spec(codebook_name)
    if spec.group_bytes != 1:
        raise ValueError(
            f"{spec.bits}-bit codebook {codebook_name!r} packs "
            f"{spec.group_codes} codes across {spec.group_bytes} bytes — "
            "there is no whole codes-per-byte factor; use pack_spec()")
    return spec.group_codes


def packed_dim(m: int, codebook_name: str) -> int:
    """Packed byte count of a logical last-axis width ``m``."""
    return pack_spec(codebook_name).packed_width(m)


def pack_codes(codes: jnp.ndarray, codebook_name: str) -> jnp.ndarray:
    """Pack uint8 code indices along the last axis into uint8 bytes, in the
    slot-major plane layout of the module docstring."""
    ps = pack_spec(codebook_name)
    if ps.group_codes == 1:
        return codes.astype(jnp.uint8)
    *lead, m = codes.shape
    planes = codes.reshape(*lead, ps.group_codes,
                           ps.packed_width(m) // ps.group_bytes)
    shifts = jnp.arange(ps.group_codes, dtype=jnp.uint32)[:, None] * ps.bits
    word = jnp.sum(planes.astype(jnp.uint32) << shifts, axis=-2)  # <= 24 bits
    byte_shifts = jnp.arange(ps.group_bytes, dtype=jnp.uint32)[:, None] * 8
    packed = (word[..., None, :] >> byte_shifts) & jnp.uint32(0xFF)
    return packed.reshape(*lead, -1).astype(jnp.uint8)


def unpack_codes(packed: jnp.ndarray, codebook_name: str) -> jnp.ndarray:
    """Inverse of :func:`pack_codes`; returns uint8 code indices."""
    ps = pack_spec(codebook_name)
    if ps.group_codes == 1:
        return packed.astype(jnp.uint8)
    *lead, mp = packed.shape
    planes = packed.reshape(*lead, ps.group_bytes,
                            ps.logical_width(mp) // ps.group_codes)
    byte_shifts = jnp.arange(ps.group_bytes, dtype=jnp.uint32)[:, None] * 8
    word = jnp.sum(planes.astype(jnp.uint32) << byte_shifts, axis=-2)
    shifts = jnp.arange(ps.group_codes, dtype=jnp.uint32)[:, None] * ps.bits
    codes = (word[..., None, :] >> shifts) & jnp.uint32(2**ps.bits - 1)
    return codes.reshape(*lead, -1).astype(jnp.uint8)


def repack_width(packed: jnp.ndarray, k: int, codebook_name: str
                 ) -> jnp.ndarray:
    """Re-lay packed rows out for a logical width of ``k`` codes: trailing
    zero codes are appended, or codes past ``k`` dropped.  A plane's width
    is ``K / group_codes``, so this is a re-pack rather than a byte pad
    whenever the group is more than one code."""
    ps = pack_spec(codebook_name)
    cur = ps.logical_width(packed.shape[-1])
    if cur == k:
        return packed
    codes = unpack_codes(packed, codebook_name)
    if k < cur:
        codes = codes[..., :k]
    else:
        widths = [(0, 0)] * (codes.ndim - 1) + [(0, k - cur)]
        codes = jnp.pad(codes, widths)
    return pack_codes(codes, codebook_name)


def fake_quant(w: jnp.ndarray, s: jnp.ndarray, codebook_name: str) -> jnp.ndarray:
    """Non-differentiable fake quantization (see qat.py for the STE version)."""
    codes = quantize_codes(w, s, codebook_name)
    return dequantize_codes(codes, s, codebook_name, dtype=w.dtype)


# ---------------------------------------------------------------------------
# Block-wise convenience wrappers (the NF4/INT4 baseline format)
# ---------------------------------------------------------------------------


def quantize_blockwise(
    w: jnp.ndarray, block_size: int, codebook_name: str
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Standard block-wise quantization -> (packed codes, block scales)."""
    from repro.core.scaling import blockwise_scales, eff_block, expand_block_scales

    block_size = eff_block(w.shape[1], block_size)
    s_blk = blockwise_scales(w, block_size)
    s = expand_block_scales(s_blk, block_size)
    codes = quantize_codes(w, s, codebook_name)
    return pack_codes(codes, codebook_name), s_blk


def dequantize_blockwise(
    packed: jnp.ndarray,
    s_blk: jnp.ndarray,
    block_size: int,
    codebook_name: str,
    dtype=jnp.float32,
) -> jnp.ndarray:
    from repro.core.scaling import expand_block_scales

    codes = unpack_codes(packed, codebook_name)
    block_size = codes.shape[-1] // s_blk.shape[-1]
    s = expand_block_scales(s_blk, block_size).astype(dtype)
    return dequantize_codes(codes, s, codebook_name, dtype=dtype)
