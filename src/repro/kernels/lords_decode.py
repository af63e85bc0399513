"""Decode-specialized fused LoRDS GEMV kernel (M ≤ 8).

Computes  y[M, N] = x[M, K] @ Ŵᵀ,   Ŵ[N, K] = lut[Q] ⊙ (B·A)

for decode-shaped workloads: a handful of tokens (one per in-flight
sequence, M ≤ 8 = one f32 sublane tile) against the full weight matrix.
This is the regime the paper's §4.4 serving claim lives in — per-token cost
is the time to *stream the weights once*, so the kernel is organized around
that invariant rather than around MXU occupancy like the prefill kernel
(:mod:`repro.kernels.lords_matmul`):

  * weight-stationary grid (N/bn, K/bk) with K innermost: every q (packed
    codes) and bT tile is fetched from HBM exactly once per call — the
    memory-roofline minimum (the prefill kernel re-streams weights once per
    M-tile; with M ≤ 8 there is exactly one M-tile, so nothing is
    re-fetched here either, but this kernel also drops the M grid axis and
    its index arithmetic),
  * the K loop is double-buffered by the Pallas grid pipeline: while tile k
    is in the MXU, the DMAs for the q tiles of k+1 are already in flight
    (two VMEM buffers per streamed operand — Pallas' automatic
    revolving-buffer pipelining over the innermost grid axis),
  * x (≤ 8 × K) and a (r × K) are held VMEM-resident for the whole call
    (constant index map; the kernel slices the live bk columns with
    ``pl.ds``) — a K-streamed BlockSpec for them would re-fetch both once
    per N-tile sweep, quietly adding up to ~(32 + 4r)/bn of the packed-q
    bytes in redundant traffic,
  * the M dimension is padded to the 8-row f32 sublane tile inside the
    wrapper, so callers can pass any M ≤ 8 without host-side padding,
  * optional out-of-kernel residual fusion: ``residual`` is added to the
    sliced result outside the kernel (XLA fuses the add into the epilogue;
    keeping it out of the kernel keeps the accumulator tile pure f32 and
    the kernel shape-agnostic about what the caller chains after it).

Per tile, for each code plane of the packed tile (its contiguous K slice
of the resident x / a — see :mod:`repro.core.quantize` for the layout):
S = bTᵀ·a  (rank-r contraction), W = lut[q] ⊙ S, acc += x·Wᵀ —
identical math to the prefill kernel, so the pure-jnp oracle
(:func:`repro.kernels.ref.lords_matmul_ref`) is the parity reference for
both.
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
    lut_select,
    plane_tiles,
)

__all__ = ["lords_decode_pallas", "DECODE_M_MAX"]

DECODE_M_MAX = 8  # one f32 sublane tile: the M-bucket this kernel serves


def _kernel(x_ref, *refs, ps, levels, eps, t, kplane):
    *q_refs, bt_ref, a_ref, o_ref = refs
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ks = pl.multiple_of(k * t, t)
    # every code plane of the packed tile in one step: plane p covers the
    # resident x/a columns p*K/g + [k*t, (k+1)*t)
    for p in range(ps.group_codes):
        cols = pl.ds(p * kplane + ks, t)
        vals = lut_select(code_plane(q_refs, ps, p), levels)  # (bn, t) f32
        s = jax.lax.dot_general(
            bt_ref[...], a_ref[:, cols], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # (bn, t)
        s = clamp_scale(s, eps)
        w = (vals * s).astype(x_ref.dtype)                    # (bn, t)
        o_ref[...] += jax.lax.dot_general(
            x_ref[:, cols], w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # (8, bn)


@functools.partial(
    jax.jit,
    static_argnames=("codebook_name", "bn", "bk", "interpret"),
)
def lords_decode_pallas(
    x: jnp.ndarray,
    q_packed: jnp.ndarray,
    b: jnp.ndarray,
    a: jnp.ndarray,
    codebook_name: str = "nf4",
    *,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
    residual: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """See module docstring.  x (M≤8, K) · dequant(q, b, a)ᵀ (+ residual)."""
    from repro.core.scaling import SCALE_EPS

    m, kdim = x.shape
    n, r = b.shape
    if m > DECODE_M_MAX:
        raise ValueError(
            f"decode kernel serves M <= {DECODE_M_MAX}, got M={m}; "
            "use lords_matmul_pallas for prefill-shaped inputs"
        )
    ps = quantize_mod.pack_spec(codebook_name)

    bn = min(bn, n)
    bk = min(bk, kdim)
    if n % bn:
        raise ValueError(
            f"shape (N={n}, K={kdim}) not divisible by blocks ({bn},{bk})"
        )
    t, nk = plane_tiles(kdim, bk, ps)
    if m < DECODE_M_MAX:  # pad M to the f32 sublane tile; sliced off below
        x = jnp.pad(x, ((0, DECODE_M_MAX - m), (0, 0)))
    grid = (n // bn, nk)  # K innermost: weights stream exactly once

    kern = functools.partial(
        _kernel, ps=ps, levels=lut_mod.static_levels(codebook_name),
        eps=SCALE_EPS, t=t, kplane=kdim // ps.group_codes,
    )
    y = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            # x and a: constant index map = fetched once, VMEM-resident
            pl.BlockSpec((DECODE_M_MAX, kdim), lambda j, k: (0, 0)),
            *byte_plane_specs(ps, bn, t, nk, lambda j, k: (j, k)),
            pl.BlockSpec((r, bn), lambda j, k: (0, j)),
            pl.BlockSpec((r, kdim), lambda j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((DECODE_M_MAX, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((DECODE_M_MAX, n), jnp.float32),
        interpret=interpret,
    )(x, *[q_packed] * ps.group_bytes, b.T, a)
    y = y[:m]
    if residual is not None:
        y = y + residual.astype(y.dtype)
    return y
