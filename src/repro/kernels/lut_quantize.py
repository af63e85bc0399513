"""Pallas TPU kernel for the LoRDS quantization step (Alg. 1, step 2.1).

    codes[i, j] = argmin_{v ∈ L} (S_ij · v − W_ij)²,   S = B·A
                = nearest-level( W_ij / S_ij )          (S² factors out)

emitted *packed* (2×4-bit / 4×2-bit per uint8, 8×3-bit per 3 bytes).  Used
inside the PTQ refinement loop and the QAT fake-quant forward, where it fuses
the S = B·A product, the division, the midpoint compare tree and the bit
packing into one VMEM pass over W.

Tiling: grid = (N/bn, g·K/bk), one code plane per K step, packed-tile
major (the slot-major layout of :mod:`repro.core.quantize`); W tile
(bn, t); bT (r, bn); a (r, t); out tiles (bn, t) uint8, one per byte plane
(``t = bk/g``).  The g plane steps of one packed tile OR their codes into
an int32 group-word scratch, and the last one writes the bytes.

Non-tile-divisible (n, kdim) are zero-padded up to the tile grid (mirroring
``dispatch.qmatmul``) and the output re-packed to the logical width; the
trailing partial pack group, if kdim is not a multiple of ``group_codes``,
keeps its deterministic padded codes (callers that slice by logical width
never read them).

The nearest-level search is a static compare tree over the L−1 midpoints
(code = Σ_l [ratio > mid_l]) — branch-free, VPU-only, no dynamic gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lut as lut_mod
from repro.core import quantize as quantize_mod
from repro.core.scaling import clamp_scale
from repro.kernels.lords_matmul import k_step, plane_tiles

__all__ = ["lut_quantize_pallas"]


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _kernel(w_ref, bt_ref, a_ref, *refs, ps, mids, eps, nk):
    *o_refs, word_ref = refs
    p, _, _ = k_step(pl.program_id(1), ps.group_codes, nk)
    s = jax.lax.dot_general(
        bt_ref[...], a_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s = clamp_scale(s, eps)
    ratio = w_ref[...].astype(jnp.float32) / s
    codes = jnp.zeros(ratio.shape, jnp.int32)
    for mid in mids:
        codes += (ratio > mid).astype(jnp.int32)
    if ps.group_codes == 1:
        o_refs[0][...] = codes.astype(jnp.uint8)
        return

    @pl.when(p == 0)
    def _first():
        word_ref[...] = codes

    @pl.when(p > 0)
    def _or():
        word_ref[...] |= codes << (ps.bits * p)

    @pl.when(p == ps.group_codes - 1)
    def _emit():
        word = word_ref[...]
        for c, o_ref in enumerate(o_refs):
            o_ref[...] = ((word >> (8 * c)) & 0xFF).astype(jnp.uint8)


@functools.partial(
    jax.jit, static_argnames=("codebook_name", "bn", "bk", "interpret")
)
def lut_quantize_pallas(
    w: jnp.ndarray,
    b: jnp.ndarray,
    a: jnp.ndarray,
    codebook_name: str = "nf4",
    *,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    from repro.core.scaling import SCALE_EPS

    n, kdim = w.shape
    _, r = b.shape
    ps = quantize_mod.pack_spec(codebook_name)
    g = ps.group_codes
    mids = lut_mod.static_midpoints(codebook_name)

    bn = min(bn, n)
    # bk % group_codes must hold on the (possibly padded) tile so every tile
    # packs whole groups
    bk = _round_up(min(bk, kdim), g)
    np_ = _round_up(n, bn)
    kp = _round_up(kdim, bk)
    if (np_, kp) != (n, kdim):
        w = jnp.pad(w, ((0, np_ - n), (0, kp - kdim)))
        b = jnp.pad(b, ((0, np_ - n), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, kp - kdim)))
    t, nk = plane_tiles(kp, bk, ps)
    grid = (np_ // bn, g * nk)
    tile = lambda kk: k_step(kk, g, nk)[2]  # noqa: E731

    kern = functools.partial(_kernel, ps=ps, mids=mids, eps=SCALE_EPS, nk=nk)
    planes = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, t), lambda i, kk: (i, tile(kk))),
            pl.BlockSpec((r, bn), lambda i, kk: (0, i)),
            pl.BlockSpec((r, t), lambda i, kk: (0, tile(kk))),
        ],
        out_specs=[pl.BlockSpec((bn, t), lambda i, kk: (i, kk // g))
                   for _ in range(ps.group_bytes)],
        out_shape=[jax.ShapeDtypeStruct((np_, kp // g), jnp.uint8)
                   for _ in range(ps.group_bytes)],
        scratch_shapes=[pltpu.VMEM((bn, t), jnp.int32)],
        interpret=interpret,
    )(w, b.T, a)
    out = jnp.concatenate(planes, axis=1)[:n]
    return quantize_mod.repack_width(out, _round_up(kdim, g), codebook_name)
