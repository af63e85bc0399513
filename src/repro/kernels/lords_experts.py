"""Grouped LoRDS matmul over an expert stack (dropless mixture of experts).

Computes, for every row tile ``i`` of ``rows`` rows,

    y[i] = x[i] @ Ŵ[e_i]ᵀ,    Ŵ[e] = lut[Q[e]] ⊙ (B[e]·A[e])

where the rows of ``x`` are a layer's routed assignments sorted by expert,
each expert's group padded to a whole number of tiles, and ``e_i`` the
expert of tile ``i`` (:func:`repro.models.moe.group_rows` builds them).
The tile → expert map, the tile → row-tile map and the tile liveness ride
in as scalar-prefetch operands, so the index maps pick each tile's expert
codes straight from the packed ``(E, N, K/g)`` stack: no per-expert
capacity buffer and no gathered weight copy.

Grid ``(tiles, N/bn, g·K/bk)``, K innermost; the tile body is
:mod:`repro.kernels.lords_matmul`'s (one code plane of one packed tile a
step, ``S = Bᵀᵀ·A`` on the MXU, ``W = lut[q] ⊙ S``, ``acc += x·Wᵀ``).
Tiles past the live ones repeat the last live tile's final block indices
and skip their body, so they fetch nothing and write nothing: the weights
of each hit expert are read once per row tile, once a call while its rows
fit one tile.

Planes whose width is not a multiple of the 128-lane tile (``K = 1408``
at nf4: planes of 704) are handled without re-packing the codes: the
caller lays ``x`` and ``A`` out with every plane zero-padded to the tile
multiple (:func:`plane_pad`), and the last code tile of a plane reads past
its end.  What it reads there meets zero activations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lut as lut_mod
from repro.core import quantize as quantize_mod
from repro.kernels import lords_matmul

__all__ = ["lords_experts_pallas", "plane_pad", "expert_tiles"]

_LANES = 128
_TILE_ELEMS = 3 << 17    # dequantized weight elements a grid step


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def expert_tiles(n: int, k: int, codebook: str) -> tuple[int, int]:
    """(bn, t): output columns and plane columns of one grid step.  ``t``
    divides the lane-padded plane width; ``bn`` is the widest divisor of
    ``n`` in whole lane tiles that keeps ``bn·t`` within the step budget
    (``n`` itself where it is not lane-aligned)."""
    ps = quantize_mod.pack_spec(codebook)
    wpad = _round_up(k // ps.group_codes, _LANES)
    t = next(c for c in (256, 128) if wpad % c == 0)
    if n % _LANES:
        return n, t
    bn = max(c for c in range(_LANES, n + 1, _LANES)
             if n % c == 0 and (c * t <= _TILE_ELEMS or c == _LANES))
    return bn, t


def plane_pad(v, k: int, codebook: str):
    """``v (..., k)`` with each of its ``g`` code planes (contiguous K
    ranges of k/g) zero-padded to the lane multiple: (..., g·wpad)."""
    ps = quantize_mod.pack_spec(codebook)
    g = ps.group_codes
    w = k // g
    wpad = _round_up(w, _LANES)
    if wpad == w:
        return v
    lead = v.shape[:-1]
    v = v.reshape(*lead, g, w)
    v = jnp.pad(v, [(0, 0)] * (len(lead) + 1) + [(0, wpad - w)])
    return v.reshape(*lead, g * wpad)


@functools.partial(
    jax.jit, static_argnames=("codebook_name", "rows", "bn", "t", "interpret"))
def lords_experts_pallas(
    x: jnp.ndarray,
    tile_expert: jnp.ndarray,
    tile_row: jnp.ndarray,
    tile_live: jnp.ndarray,
    q_packed: jnp.ndarray,
    bt: jnp.ndarray,
    a: jnp.ndarray,
    codebook_name: str = "nf4",
    *,
    rows: int,
    bn: int,
    t: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """x (tiles·rows, g·wpad) plane-padded rows grouped by expert;
    tile_expert / tile_row / tile_live (tiles,) int32; q_packed (E, N, W)
    packed codes; bt (E, r, N) = Bᵀ per expert; a (E, r, g·wpad)
    plane-padded → y (tiles·rows, N) f32 (rows of dead tiles unset)."""
    from repro.core.scaling import SCALE_EPS

    m, kpad = x.shape
    _, n, _ = q_packed.shape
    r = bt.shape[1]
    ps = quantize_mod.pack_spec(codebook_name)
    g = ps.group_codes
    if ps.group_bytes != 1:
        raise NotImplementedError("grouped expert kernel: byte-aligned "
                                  "code groups only")
    n_tiles = tile_expert.shape[0]
    if m != n_tiles * rows or n % bn or kpad % (g * t):
        raise ValueError(f"x {x.shape}, {n_tiles} tiles of {rows} rows, "
                         f"N={n} / bn={bn}, plane tile t={t}")
    nk = kpad // (g * t)
    nj = n // bn
    last = g * nk - 1

    def eff(j, kk, live):
        # a dead tile repeats the last live tile's final block indices
        return (jnp.where(live == 1, j, nj - 1),
                jnp.where(live == 1, kk, last))

    def x_map(i, j, kk, te, tr, tl):
        _, kk = eff(j, kk, tl[i])
        p, k = kk % g, kk // g
        return tr[i], p * nk + k

    def q_map(i, j, kk, te, tr, tl):
        j, kk = eff(j, kk, tl[i])
        return te[i], j, kk // g

    def bt_map(i, j, kk, te, tr, tl):
        j, _ = eff(j, kk, tl[i])
        return te[i], 0, j

    def a_map(i, j, kk, te, tr, tl):
        _, kk = eff(j, kk, tl[i])
        p, k = kk % g, kk // g
        return te[i], 0, p * nk + k

    def o_map(i, j, kk, te, tr, tl):
        j, _ = eff(j, kk, tl[i])
        return tr[i], j

    body = functools.partial(
        lords_matmul.tile_body, ps=ps,
        levels=lut_mod.static_levels(codebook_name), eps=SCALE_EPS, nk=nk)

    def kern(te_ref, tr_ref, tl_ref, *refs):
        kk = pl.program_id(2)   # read outside the branch (interpret mode)

        @pl.when(tl_ref[pl.program_id(0)] == 1)
        def _():
            body(*refs, kk=kk)

    sq = pl.Squeezed()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_tiles, nj, g * nk),
        in_specs=[
            pl.BlockSpec((rows, t), x_map),
            pl.BlockSpec((sq, bn, t), q_map),
            pl.BlockSpec((sq, r, bn), bt_map),
            pl.BlockSpec((sq, r, t), a_map),
        ],
        out_specs=pl.BlockSpec((rows, bn), o_map),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
        name="lords_experts_pallas",
    )(tile_expert.astype(jnp.int32), tile_row.astype(jnp.int32),
      tile_live.astype(jnp.int32), x, q_packed, bt, a)
