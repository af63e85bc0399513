"""Fused LoRDS dequant-matmul Pallas TPU kernel.

Computes  y[M, N] = x[M, K] @ Ŵᵀ,   Ŵ[N, K] = lut[Q] ⊙ (B·A)

with Q stored packed (2×4-bit / 4×2-bit codes per uint8, or 8×3-bit codes
per 3 bytes) in HBM.  This is
the TPU analogue of the paper's Triton kernel (§4.4): the low-rank scale
product rides along with each weight tile, so dequantization adds no extra
HBM traffic beyond the packed codes themselves — the entire reason LoRDS
serving matches block-wise NF4 speed while QLoRA pays for an extra adapter
GEMM.

Packed codes are laid out in slot-major planes (:mod:`repro.core.quantize`):
code slot ``p`` of a packed column tile holds the contiguous logical K range
``p*K/g + [k*t, (k+1)*t)``.  So one grid step takes one plane of one packed
tile — a shift/mask of the byte tile — against the matching x / A columns.

Tiling (all VMEM), with ``g`` codes per group and ``t = bk/g``:
  grid = (M/bm, N/bn, g·K/bk), K innermost for accumulation, ordered so the
  g plane steps of one packed tile are consecutive (fetched once)
    x tile   (bm, t)             input activations of the step's plane
    q tiles  (bn, t) uint8 ×B    packed codes, one tile per byte plane
    bT tile  (r, bn)             scale factor B, transposed so the tiny rank
    a tile   (r, t)              dim sits in sublanes (lane dim stays 128-al.)
    out tile (bm, bn) f32        accumulated across the K grid axis

Per step:  S = bTᵀ·a  (r-contraction, r ≤ 32), W = lut[q]⊙S, acc += x·Wᵀ.
The MXU sees two matmuls: the tiny (bn×r)×(r×t) scale product and the main
(bm×t)×(t×bn) GEMM — dequant itself is pure VPU elementwise work.

Weight-stationary layout note: with grid order (i, j, k) the q/bT/a tiles are
re-fetched for every i; for decode (M small → one i) this is optimal
(weights stream exactly once — the memory-roofline minimum).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import lut as lut_mod
from repro.core import quantize as quantize_mod
from repro.core.scaling import clamp_scale

__all__ = ["lords_matmul_pallas"]


def plane_tiles(kdim: int, bk: int, ps: quantize_mod.PackSpec):
    """``(t, nk)`` for a K extent of ``kdim`` codes in ``bk``-code tiles:
    each tile is ``g`` plane slices of ``t = bk/g`` columns, and each plane
    spans ``nk = kdim/bk`` of them."""
    g = ps.group_codes
    if kdim % bk or bk % g:
        raise ValueError(f"K={kdim} not divisible by tile bk={bk}, or bk not "
                         f"a multiple of the {g}-code pack group")
    return bk // g, kdim // bk


def k_step(kk, g: int, nk: int):
    """Grid step ``kk`` → (plane, packed tile, logical K tile).  Steps run
    packed-tile major, so the ``g`` steps reading one packed tile are
    consecutive and Pallas fetches that tile once."""
    p, k = kk % g, kk // g
    return p, k, p * nk + k


def byte_plane_specs(ps, bn: int, t: int, nk: int, index):
    """BlockSpecs of the ``group_bytes`` byte planes of a packed operand;
    ``index(grid..) -> (row tile, packed tile)``."""
    def spec(c):
        def imap(*grid):
            j, k = index(*grid)
            return j, c * nk + k
        return pl.BlockSpec((bn, t), imap)
    return [spec(c) for c in range(ps.group_bytes)]


def code_plane(q_refs, ps, p):
    """Code plane ``p`` (static or traced) of the byte-plane tiles: the
    ``(bn, t)`` int32 codes of one contiguous logical K slice.  Pure VPU
    shift/mask work — no lane interleave, no full-width code array."""
    word = q_refs[0][...].astype(jnp.int32)
    for c in range(1, ps.group_bytes):
        word = word | (q_refs[c][...].astype(jnp.int32) << (8 * c))
    if ps.group_codes == 1:
        return word
    return (word >> (ps.bits * p)) & ((1 << ps.bits) - 1)


def lut_select(codes, levels: tuple[float, ...]):
    """``levels[codes]`` as a bit-tree of selects: bit b of the code picks
    between the two halves of each 2^(b+1)-level subtree.  L−1 selects over
    log2(L) bit masks, evaluated depth first so only a handful of tiles are
    live; the levels are scalar constants (Mosaic-friendly, no gather)."""
    n_levels = len(levels)
    nbits = max((n_levels - 1).bit_length(), 1)
    bit = [(codes & (1 << b)) != 0 for b in range(nbits)]

    def pick(lo: int, b: int):
        if b < 0:
            return jnp.float32(levels[min(lo, n_levels - 1)])
        half = 1 << b
        if lo + half >= n_levels:  # upper half unused (non power-of-2 L)
            return pick(lo, b - 1)
        return jnp.where(bit[b], pick(lo + half, b - 1), pick(lo, b - 1))

    return jnp.broadcast_to(pick(0, nbits - 1), codes.shape)


def _kernel(x_ref, *refs, ps, levels, eps, nk):
    *q_refs, bt_ref, a_ref, o_ref = refs
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    p, _, _ = k_step(kk, ps.group_codes, nk)
    vals = lut_select(code_plane(q_refs, ps, p), levels)      # (bn, t) f32
    # low-rank scale tile: S = Bᵀᵀ·A  -> (bn, t), r-contraction on the MXU
    s = jax.lax.dot_general(
        bt_ref[...], a_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s = clamp_scale(s, eps)
    w = (vals * s).astype(x_ref.dtype)                        # (bn, t)
    acc = jax.lax.dot_general(
        x_ref[...], w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                          # (bm, bn)
    o_ref[...] += acc


@functools.partial(
    jax.jit,
    static_argnames=("codebook_name", "bm", "bn", "bk", "interpret"),
)
def lords_matmul_pallas(
    x: jnp.ndarray,
    q_packed: jnp.ndarray,
    b: jnp.ndarray,
    a: jnp.ndarray,
    codebook_name: str = "nf4",
    *,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """See module docstring.  x (M,K) · dequant(q (N,K/pack), b (N,r), a (r,K))ᵀ."""
    from repro.core.scaling import SCALE_EPS

    m, kdim = x.shape
    n, r = b.shape
    ps = quantize_mod.pack_spec(codebook_name)
    g = ps.group_codes

    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, kdim)
    if m % bm or n % bn:
        raise ValueError(
            f"shape ({m},{n},{kdim}) not divisible by blocks ({bm},{bn},{bk})"
        )
    t, nk = plane_tiles(kdim, bk, ps)
    grid = (m // bm, n // bn, g * nk)
    tile = lambda kk: k_step(kk, g, nk)[2]  # noqa: E731

    kern = functools.partial(
        _kernel, ps=ps, levels=lut_mod.static_levels(codebook_name),
        eps=SCALE_EPS, nk=nk,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, t), lambda i, j, kk: (i, tile(kk))),
            *byte_plane_specs(ps, bn, t, nk,
                              lambda i, j, kk: (j, kk // g)),
            pl.BlockSpec((r, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((r, t), lambda i, j, kk: (0, tile(kk))),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, *[q_packed] * ps.group_bytes, b.T, a)
