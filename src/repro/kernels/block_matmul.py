"""Block-wise (bitsandbytes-style) dequant-matmul Pallas kernel — baseline.

Same contract as :mod:`repro.kernels.lords_matmul` but with piecewise-constant
block scales instead of the low-rank S = B·A.  Exists so the Fig.-2 style
kernel comparison (bnb-NF4 vs QLoRA vs LoRDS) is apples-to-apples on TPU.
Shares the plane unpack and the bit-tree LUT select with the lords kernels
(:mod:`repro.kernels.lords_matmul`).

y[M,N] = x[M,K] @ (lut[Q] ⊙ repeat(s_blk))ᵀ
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import lut as lut_mod
from repro.core import quantize as quantize_mod
from repro.kernels.lords_matmul import (
    byte_plane_specs,
    code_plane,
    k_step,
    lut_select,
    plane_tiles,
)
from repro.kernels.lords_matmul_t import (
    block_scale_spec,
    expand_scales,
    scales_view,
)

__all__ = ["block_matmul_pallas"]


def _kernel(x_ref, *refs, ps, levels, reps, nk):
    *q_refs, s_ref, o_ref = refs
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    p, _, _ = k_step(kk, ps.group_codes, nk)
    vals = lut_select(code_plane(q_refs, ps, p), levels)
    w = (vals * expand_scales(s_ref[0], reps, vals.shape[1])).astype(
        x_ref.dtype)
    o_ref[...] += jax.lax.dot_general(
        x_ref[...], w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "codebook_name", "bm", "bn", "bk",
                     "interpret"),
)
def block_matmul_pallas(
    x: jnp.ndarray,
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
    m, kdim = x.shape
    n = q_packed.shape[0]
    ps = quantize_mod.pack_spec(codebook_name)
    g = ps.group_codes

    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kdim)
    if m % bm or n % bn:
        raise ValueError(f"({m},{n},{kdim}) not divisible by ({bm},{bn},{bk})")
    t, nk = plane_tiles(kdim, bk, ps)
    grid = (m // bm, n // bn, g * nk)
    tile = lambda i, j, kk: k_step(kk, g, nk)[2]  # noqa: E731
    s_spec, c, reps = block_scale_spec(bn, t, block_size, tile,
                                       lambda i, j, kk: j)

    kern = functools.partial(_kernel, ps=ps,
                             levels=lut_mod.static_levels(codebook_name),
                             reps=reps, nk=nk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, t), lambda *grid: (grid[0], tile(*grid))),
            *byte_plane_specs(ps, bn, t, nk,
                              lambda i, j, kk: (j, kk // g)),
            s_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, *[q_packed] * ps.group_bytes, scales_view(s_blk, c))
