"""Fused LoRDS gradient-reduction Pallas kernels (training backward).

Given upstream gradient ``g[M, N]`` and activations ``x[M, K]``, the LoRDS
parameter gradients all factor through the weight-space cotangent

    ∂L/∂Ŵ = gᵀ·x                                    (N, K)

which the dense backward used to materialize in f32 alongside a second
dequantized Ŵ.  These kernels instead accumulate ∂L/∂Ŵ *tile by tile* in a
VMEM scratch (never HBM) and collapse it straight into the small outputs:

  frozen / peft (multiplicative PEFT, paper §3.4):
      ∂S = ∂L/∂Ŵ ⊙ lut[Q] ⊙ 1[|S| ≥ eps]            clamp mask in-kernel
      dB = ∂S·Aᵀ   (N, r)      dA = Bᵀ·∂S   (r, K)

  qat (STE, paper Eq. 4/5):
      dW = ∂L/∂Ŵ                                     Eq. 4 (identity)
      ∂S = ∂L/∂Ŵ ⊙ (lut[Q] − W ⊘ S) ⊙ 1[|S| ≥ eps]  Eq. 5
      dB / dA as above

Tiling:  grid = (N/bn, g·K/bk, M/bm), M innermost (the ∂L/∂Ŵ reduction);
each K step is one code plane of a packed tile, as in
:mod:`repro.kernels.lords_matmul` (``t = bk/g`` logical columns).
Per (j, k) tile the scratch ``acc`` (bn, t) f32 accumulates gᵀ·x over the
M axis; at the last M step the tile is dequant-masked and contracted on the
MXU into the rank-space outputs.  The q/bT/a (and W for qat) tiles have
M-independent index maps, so Pallas fetches each exactly once per (j, k) —
codes stream from HBM once per call.

Outputs (f32, padded shapes — callers slice):
  dbT     (r, N)             B-gradient, transposed so the rank dim sits in
                             sublanes; resident in VMEM for a whole j row
                             (its index map is constant across k and m)
  da_part (N/bn, r, K)       per-N-tile partial A-gradients — summed over
                             axis 0 by the caller (a (N/bn)·r·K f32 array,
                             ~r/bn of one weight matrix: negligible)
  dW      (N, K) [qat only]  the master-weight gradient itself (a parameter
                             gradient the optimizer owns — not a temporary)

``block_grad_pallas`` is the block-wise analogue: ∂s_blk = per-block sums of
∂L/∂Ŵ ⊙ lut[Q], with the same scratch-accumulation structure (no clamp mask
— block scales are absmax-initialized away from zero).
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
from repro.kernels.lords_matmul import (
    byte_plane_specs,
    code_plane,
    k_step,
    lut_select,
    plane_tiles,
)
from repro.kernels.lords_matmul_t import block_scale_spec

__all__ = ["lords_grad_pallas", "block_grad_pallas"]


def _kernel(x_ref, g_ref, *refs, ps, levels, eps, nk, qat):
    if qat:
        *q_refs, bt_ref, a_ref, w_ref, dbt_ref, dap_ref, dw_ref, acc_ref = refs
    else:
        *q_refs, bt_ref, a_ref, dbt_ref, dap_ref, acc_ref = refs
    kk, m = pl.program_id(1), pl.program_id(2)
    nm = pl.num_programs(2)

    @pl.when(m == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(kk == 0, m == 0))
    def _zero_dbt():  # dbT tile is resident across the whole (kk, m) sweep
        dbt_ref[...] = jnp.zeros_like(dbt_ref)

    acc_ref[...] += jax.lax.dot_general(
        g_ref[...], x_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                          # ∂L/∂Ŵ (bn, t)

    @pl.when(m == nm - 1)
    def _reduce():
        p, _, _ = k_step(kk, ps.group_codes, nk)
        vals = lut_select(code_plane(q_refs, ps, p), levels)   # (bn, t) f32
        s_raw = jax.lax.dot_general(
            bt_ref[...], a_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        mask = (jnp.abs(s_raw) >= eps).astype(jnp.float32)
        dw_hat = acc_ref[...]
        if not qat:                                            # frozen / peft
            ds = dw_hat * vals * mask
        else:                                                  # qat STE
            s = clamp_scale(s_raw, eps)
            resid = vals - w_ref[...].astype(jnp.float32) / s  # Q − W ⊘ S
            ds = dw_hat * resid * mask                         # Eq. 5
            dw_ref[...] = dw_hat                               # Eq. 4
        # rank-space contractions: dBᵀ = A·∂Sᵀ, dA-partial = Bᵀ·∂S
        dbt_ref[...] += jax.lax.dot_general(
            a_ref[...], ds, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # (r, bn)
        dap_ref[...] = jax.lax.dot_general(
            bt_ref[...], ds, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )[None]                                                # (1, r, t)


@functools.partial(
    jax.jit,
    static_argnames=("codebook_name", "bm", "bn", "bk", "interpret"),
)
def lords_grad_pallas(
    x: jnp.ndarray,
    g: jnp.ndarray,
    q_packed: jnp.ndarray,
    b: jnp.ndarray,
    a: jnp.ndarray,
    codebook_name: str = "nf4",
    *,
    w: jnp.ndarray | None = None,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
):
    """See module docstring.  Returns ``(dbT (r,N), da_part (N/bn,r,K))``
    plus ``dW (N,K)`` when the qat master weight ``w`` is given."""
    from repro.core.scaling import SCALE_EPS

    m, kdim = x.shape
    n, r = b.shape
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
    grid = (n // bn, gc * nk, m // bm)  # M innermost: the ∂L/∂Ŵ reduction
    tile = lambda kk: k_step(kk, gc, nk)[2]  # noqa: E731

    qat = w is not None
    kern = functools.partial(
        _kernel, ps=ps, levels=lut_mod.static_levels(codebook_name),
        eps=SCALE_EPS, nk=nk, qat=qat,
    )
    in_specs = [
        pl.BlockSpec((bm, t), lambda j, kk, m: (m, tile(kk))),    # x
        pl.BlockSpec((bm, bn), lambda j, kk, m: (m, j)),          # g
        *byte_plane_specs(ps, bn, t, nk,
                          lambda j, kk, m: (j, kk // gc)),        # q
        pl.BlockSpec((r, bn), lambda j, kk, m: (0, j)),           # bT
        pl.BlockSpec((r, t), lambda j, kk, m: (0, tile(kk))),     # a
    ]
    inputs = [x, g, *[q_packed] * ps.group_bytes, b.T, a]
    out_specs = [
        pl.BlockSpec((r, bn), lambda j, kk, m: (0, j)),           # dbT
        pl.BlockSpec((1, r, t), lambda j, kk, m: (j, 0, tile(kk))),  # da_part
    ]
    out_shape = [
        jax.ShapeDtypeStruct((r, n), jnp.float32),
        jax.ShapeDtypeStruct((n // bn, r, kdim), jnp.float32),
    ]
    if qat:
        in_specs.append(
            pl.BlockSpec((bn, t), lambda j, kk, m: (j, tile(kk))))  # w
        inputs.append(w)
        out_specs.append(
            pl.BlockSpec((bn, t), lambda j, kk, m: (j, tile(kk))))  # dW
        out_shape.append(jax.ShapeDtypeStruct((n, kdim), jnp.float32))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bn, t), jnp.float32)],
        interpret=interpret,
    )(*inputs)


# ---------------------------------------------------------------------------
# Block-wise baseline:  ∂s_blk = per-block sums of (gᵀ·x) ⊙ lut[Q]
# ---------------------------------------------------------------------------


def _block_body(x_ref, g_ref, *refs, ps, levels, nk, group,
                blocks_per_tile):
    *q_refs, o_ref, acc_ref = refs
    kk, m = pl.program_id(1), pl.program_id(2)
    nm = pl.num_programs(2)

    @pl.when(m == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(kk % group == 0, m == 0))
    def _zero_out():  # out tile is resident for `group` consecutive k steps
        o_ref[...] = jnp.zeros_like(o_ref)

    acc_ref[...] += jax.lax.dot_general(
        g_ref[...], x_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(m == nm - 1)
    def _reduce():
        vals = lut_select(code_plane(q_refs, ps, kk // nk), levels)
        ds = acc_ref[...] * vals                               # (bn, t)
        bn, t = ds.shape
        o_ref[0] += ds.reshape(bn, blocks_per_tile,
                               t // blocks_per_tile).sum(-1)


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "codebook_name", "bm", "bn", "bk",
                     "interpret"),
)
def block_grad_pallas(
    x: jnp.ndarray,
    g: jnp.ndarray,
    q_packed: jnp.ndarray,
    block_size: int,
    codebook_name: str = "nf4",
    *,
    bm: int = 128,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """∂s_blk (N, K/block_size) for the block-wise dequant matmul.

    Steps walk the logical K tiles in order (plane ``kk // nk``, packed
    tile ``kk % nk``) so a block spanning several tiles accumulates into
    its resident output column on consecutive steps."""
    m, kdim = x.shape
    n = q_packed.shape[0]
    ps = quantize_mod.pack_spec(codebook_name)

    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kdim)
    if m % bm or n % bn:
        raise ValueError(
            f"shape ({m},{n},{kdim}) not divisible by blocks ({bm},{bn},{bk})"
        )
    t, nk = plane_tiles(kdim, bk, ps)
    grid = (n // bn, ps.group_codes * nk, m // bm)
    s_spec, c, _ = block_scale_spec(bn, t, block_size, lambda j, kk, m: kk,
                                    lambda j, kk, m: j)
    group = max(block_size // t, 1)        # tiles sharing one block column
    blocks_per_tile = max(t // block_size, 1)

    kern = functools.partial(_block_body, ps=ps,
                             levels=lut_mod.static_levels(codebook_name), nk=nk,
                             group=group, blocks_per_tile=blocks_per_tile)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, t), lambda j, kk, m: (m, kk)),
            pl.BlockSpec((bm, bn), lambda j, kk, m: (m, j)),
            *byte_plane_specs(ps, bn, t, nk,
                              lambda j, kk, m: (j, kk % nk)),
        ],
        out_specs=s_spec,
        out_shape=jax.ShapeDtypeStruct(
            (kdim // (c * block_size), n, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, t), jnp.float32)],
        interpret=interpret,
    )(x, g, *[q_packed] * ps.group_bytes)
    return out.transpose(1, 0, 2).reshape(n, kdim // block_size)
