"""Fused *transposed* LoRDS dequant-matmul Pallas kernels (training backward).

Computes  dx[M, K] = g[M, N] @ Ŵ,   Ŵ[N, K] = lut[Q] ⊙ (B·A)

directly from the packed codes — the activation-gradient half of the LoRDS
backward pass.  Together with :mod:`repro.kernels.lords_grad` this is what
lets QAT/PEFT training never materialize Ŵ: the forward streams Q once
(:mod:`repro.kernels.lords_matmul`), the backward streams it twice (here for
dx, there for the parameter gradients), and no (N, K) f32 dequantized
temporary ever exists in HBM.

Tiling (all VMEM), one code plane of a packed tile per K step as in
:mod:`repro.kernels.lords_matmul` (``t = bk/g`` logical columns):
  grid = (M/bm, g·K/bk, N/bn), N innermost for accumulation
    g tile   (bm, bn)            output-side gradient
    q tiles  (bn, t) uint8 ×B    packed byte planes — streamed once per
                                 (M-tile, plane)
    bT tile  (r, bn)             scale factor B, transposed (rank in sublanes)
    a tile   (r, t)              constant index across the N loop → fetched
                                 once per K-tile and VMEM-resident after that
    out tile (bm, t) f32         accumulated across the N grid axis

Per tile:  S = bTᵀ·a (rank-r MXU contraction), W = lut[q] ⊙ S (the same
bit-tree select as the forward kernels), acc += g·W — note W is
used *untransposed* here: the (bn, t) dequant tile is exactly the operand
layout ``g @ Ŵ`` wants, so transposition costs nothing.  The innermost
(reduction) grid axis is double-buffered by the Pallas pipeline exactly as
in :mod:`repro.kernels.lords_decode`: the q DMAs for tile n+1 are in flight
while tile n is in the MXU.

``block_matmul_t_pallas`` is the block-wise analogue (piecewise-constant
scales instead of S = B·A) used by the blockwise/qlora-family backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import lut as lut_mod
from repro.core import quantize as quantize_mod
from repro.core.scaling import clamp_scale
from repro.kernels.lords_matmul import (
    byte_plane_specs,
    code_plane,
    k_step,
    lut_select,
    plane_tiles,
)

__all__ = ["lords_matmul_t_pallas", "block_matmul_t_pallas"]


def _kernel(g_ref, *refs, ps, levels, eps, nk):
    *q_refs, bt_ref, a_ref, o_ref = refs
    nn = pl.program_id(2)

    @pl.when(nn == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    p, _, _ = k_step(pl.program_id(1), ps.group_codes, nk)
    vals = lut_select(code_plane(q_refs, ps, p), levels)      # (bn, t) f32
    s = jax.lax.dot_general(
        bt_ref[...], a_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                          # (bn, t)
    s = clamp_scale(s, eps)
    w = (vals * s).astype(g_ref.dtype)                        # (bn, t)
    o_ref[...] += jax.lax.dot_general(
        g_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                          # (bm, t)


@functools.partial(
    jax.jit,
    static_argnames=("codebook_name", "bm", "bn", "bk", "interpret"),
)
def lords_matmul_t_pallas(
    g: jnp.ndarray,
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
    """See module docstring.  g (M,N) · dequant(q (N,K/pack), b (N,r), a (r,K))."""
    from repro.core.scaling import SCALE_EPS

    m, n = g.shape
    _, r = b.shape
    kdim = a.shape[1]
    ps = quantize_mod.pack_spec(codebook_name)
    gc = ps.group_codes

    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, kdim)
    if m % bm or n % bn:
        raise ValueError(
            f"shape ({m},{n},{kdim}) not divisible by blocks ({bm},{bn},{bk})"
        )
    t, nk = plane_tiles(kdim, bk, ps)
    grid = (m // bm, gc * nk, n // bn)  # N innermost: the reduction axis
    tile = lambda kk: k_step(kk, gc, nk)[2]  # noqa: E731

    kern = functools.partial(
        _kernel, ps=ps, levels=lut_mod.static_levels(codebook_name),
        eps=SCALE_EPS, nk=nk,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, kk, nn: (i, nn)),
            *byte_plane_specs(ps, bn, t, nk,
                              lambda i, kk, nn: (nn, kk // gc)),
            pl.BlockSpec((r, bn), lambda i, kk, nn: (0, nn)),
            pl.BlockSpec((r, t), lambda i, kk, nn: (0, tile(kk))),
        ],
        out_specs=pl.BlockSpec((bm, t), lambda i, kk, nn: (i, tile(kk))),
        out_shape=jax.ShapeDtypeStruct((m, kdim), jnp.float32),
        interpret=interpret,
    )(g, *[q_packed] * ps.group_bytes, b.T, a)


# ---------------------------------------------------------------------------
# Block-wise transposed baseline:  dx = g @ (lut[Q] ⊙ repeat(s_blk))
# ---------------------------------------------------------------------------


def block_scale_spec(bn: int, t: int, block_size: int, tile, row):
    """``(spec, c, reps)`` of the block scales as a kernel operand.

    The (N, K/bs) scales travel as :func:`scales_view` (K/(c·bs), N, c),
    c = max(t/bs, 1) scales per step — so a step's (bn, c) tile spans its
    block's full lane extent — indexed by the step's logical K tile
    ``tile(grid..)`` (t columns) and row tile ``row(grid..)``.  A tile
    holds whole blocks (t % bs == 0) or sits inside one; ``reps`` is the
    per-scale column repeat within the tile."""
    if t % block_size and block_size % t:
        raise ValueError(f"plane tile {t} incompatible with block_size "
                         f"{block_size}")
    c = max(t // block_size, 1)
    spec = pl.BlockSpec(
        (1, bn, c),
        lambda *grid: (tile(*grid) * t // (c * block_size), row(*grid), 0))
    return spec, c, min(t, block_size)


def scales_view(s_blk, c: int):
    """(N, K/bs) block scales → the (K/(c·bs), N, c) kernel operand."""
    n = s_blk.shape[0]
    return s_blk.astype(jnp.float32).reshape(n, -1, c).transpose(1, 0, 2)


def expand_scales(s, reps: int, width: int):
    """(bn, nblk) block-scale tile → (bn, width) per-column scales."""
    bn, nblk = s.shape
    s_full = jnp.broadcast_to(s[:, :, None], (bn, nblk, reps)).reshape(
        bn, nblk * reps)
    if s_full.shape[1] != width:  # one block spans the whole tile
        s_full = jnp.broadcast_to(s, (bn, width))
    return s_full


def _block_kernel(g_ref, *refs, ps, levels, reps, nk):
    *q_refs, s_ref, o_ref = refs
    nn = pl.program_id(2)

    @pl.when(nn == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    p, _, _ = k_step(pl.program_id(1), ps.group_codes, nk)
    vals = lut_select(code_plane(q_refs, ps, p), levels)
    w = (vals * expand_scales(s_ref[0], reps, vals.shape[1])).astype(
        g_ref.dtype)
    o_ref[...] += jax.lax.dot_general(
        g_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "codebook_name", "bm", "bn", "bk",
                     "interpret"),
)
def block_matmul_t_pallas(
    g: jnp.ndarray,
    q_packed: jnp.ndarray,
    s_blk: jnp.ndarray,
    block_size: int,
    codebook_name: str = "nf4",
    *,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    m, n = g.shape
    ps = quantize_mod.pack_spec(codebook_name)
    gc = ps.group_codes
    kdim = ps.logical_width(q_packed.shape[1])

    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kdim)
    if m % bm or n % bn:
        raise ValueError(f"({m},{n},{kdim}) not divisible by ({bm},{bn},{bk})")
    t, nk = plane_tiles(kdim, bk, ps)
    grid = (m // bm, gc * nk, n // bn)
    tile = lambda i, kk, nn: k_step(kk, gc, nk)[2]  # noqa: E731
    s_spec, c, reps = block_scale_spec(bn, t, block_size, tile,
                                       lambda i, kk, nn: nn)

    kern = functools.partial(_block_kernel, ps=ps,
                             levels=lut_mod.static_levels(codebook_name),
                             reps=reps, nk=nk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, kk, nn: (i, nn)),
            *byte_plane_specs(ps, bn, t, nk,
                              lambda i, kk, nn: (nn, kk // gc)),
            s_spec,
        ],
        out_specs=pl.BlockSpec((bm, t), lambda *grid: (grid[0], tile(*grid))),
        out_shape=jax.ShapeDtypeStruct((m, kdim), jnp.float32),
        interpret=interpret,
    )(g, *[q_packed] * ps.group_bytes, scales_view(s_blk, c))
