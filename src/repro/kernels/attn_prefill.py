"""Fused flash-style causal prefill attention Pallas kernel.

Computes  O = softmax(mask(Q·Kᵀ · scale)) · V  per (batch, head) without
ever materializing the (s, S) score matrix: the KV sequence is streamed in
``bkv``-sized tiles with the classic online-softmax recurrence (running max
``m``, running exp-sum ``l``, unnormalized accumulator ``acc`` — flash-2
style: the 1/l normalization happens once, on the last KV tile).  This is
the prefill analogue of the lords_matmul family — the portable einsum path
in :func:`repro.models.attention.chunked_causal_attention` stays as the
ref oracle, but peaks at a (b, nh, chunk, S) f32 temporary the kernel
never creates.

Layout / tiling — all operands are indexed in the model's native
(batch, seq, heads, head_dim) layout, viewed as (batch, seq, heads·head_dim)
(a free reshape, no transpose copy), so a head is a lane-aligned hd-wide
column block:
  grid = (b, nh, s/bq, S/bkv), KV innermost (the online-softmax reduction)
    q tile    (1, bq, hd)       — constant over the KV axis (VMEM-resident
                                  per Q tile)
    k/v tile  (1, bkv, hd)      — head-indexed ``h // group`` so GQA heads
                                  read their shared KV head straight from
                                  the unexpanded (b, S, nkv, hd) arrays:
                                  the head-group broadcast costs zero HBM
                                  traffic (the portable path jnp.repeats
                                  K/V to the full head count first)
    qpos tile (1, bq, 1) int32  — per-token positions, so ragged /
    kpos tile (1, 1, bkv) int32   shifted sequences mask correctly; -1
                                  marks dead (padding) rows
    m/l scratch (bq, 128) f32   — lane-replicated running max / exp-sum
    acc scratch (bq, hd)  f32   — unnormalized output accumulator

Masking uses the finite ``ATTN_NEG_INF`` (-1e30), and the per-tile p is
zeroed through the liveness mask itself: a fully-masked tile contributes
exactly nothing (no exp(0) junk to correct), fully-dead padding rows keep
l = 0 and are zeroed by the final where(l == 0) guard, and no -inf - -inf
NaNs can arise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import ATTN_NEG_INF

__all__ = ["attn_prefill_pallas"]

_STAT_LANES = 128  # lane width of the m/l scratch tiles


def _kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale, nk):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, ATTN_NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale                 # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                         # (bkv, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                        # (bq, bkv)
    qpos = qpos_ref[0]                                       # (bq, 1)
    kpos = kpos_ref[0]                                       # (1, bkv)
    live = (kpos <= qpos) & (kpos >= 0)                      # (bq, bkv)
    s = jnp.where(live, s, ATTN_NEG_INF)

    m_prev = m_ref[:, :1]                                    # (bq, 1)
    l_prev = l_ref[:, :1]
    m_curr = jnp.max(s, axis=1, keepdims=True)               # (bq, 1)
    m_next = jnp.maximum(m_prev, m_curr)
    alpha = jnp.exp(m_prev - m_next)                         # (bq, 1)
    # liveness-zeroed weights: a fully-masked tile (all s == NEG_INF ==
    # m_next) would otherwise yield p = exp(0) = 1 junk, leaving dead
    # rows with l = S instead of 0
    p = jnp.exp(s - m_next) * live.astype(jnp.float32)       # (bq, bkv)
    l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_next, l_ref.shape)

    v = v_ref[0].astype(jnp.float32)                         # (bkv, hd)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _store():
        l = l_ref[:, :1]
        inv = jnp.where(l == 0.0, 0.0, 1.0 / l)              # dead rows -> 0
        o_ref[0] = acc_ref[...] * inv


@functools.partial(
    jax.jit, static_argnames=("logit_scale", "bq", "bkv", "interpret"))
def attn_prefill_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    qpos: jnp.ndarray,
    kpos: jnp.ndarray,
    *,
    logit_scale: float,
    bq: int = 128,
    bkv: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """q (b, s, nh, hd) · k/v (b, S, nkv, hd) → (b, s, nh, hd_v) f32.

    Operands stay in the model's native layout; the index maps do the
    per-head tiling.  ``qpos`` (b, s) / ``kpos`` (b, S) int32 positions
    drive the causal mask (-1 = dead row, output zeroed).  s/S must divide
    bq/bkv — the dispatch layer pads and sets padded positions to -1.
    """
    b, s, nh, hd = q.shape
    cap, nkv = k.shape[1], k.shape[2]
    hdv = v.shape[-1]
    group = nh // nkv
    bq = min(bq, s)
    bkv = min(bkv, cap)
    if s % bq or cap % bkv:
        raise ValueError(
            f"seq lengths (s={s}, S={cap}) not divisible by tiles "
            f"({bq},{bkv})")
    nk = cap // bkv
    grid = (b, nh, s // bq, nk)

    kern = functools.partial(_kernel, scale=float(logit_scale), nk=nk)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bi, hi, qi, ki: (bi, qi, hi)),
            # GQA broadcast in the index map: head hi reads KV head hi//g
            pl.BlockSpec((1, bkv, hd),
                         lambda bi, hi, qi, ki: (bi, ki, hi // group)),
            pl.BlockSpec((1, bkv, hdv),
                         lambda bi, hi, qi, ki: (bi, ki, hi // group)),
            pl.BlockSpec((1, bq, 1), lambda bi, hi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, 1, bkv), lambda bi, hi, qi, ki: (bi, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, bq, hdv),
                               lambda bi, hi, qi, ki: (bi, qi, hi)),
        out_shape=jax.ShapeDtypeStruct((b, s, nh * hdv), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, _STAT_LANES), jnp.float32),
            pltpu.VMEM((bq, _STAT_LANES), jnp.float32),
            pltpu.VMEM((bq, hdv), jnp.float32),
        ],
        interpret=interpret,
    )(q.reshape(b, s, nh * hd), k.reshape(b, cap, nkv * hd),
      v.reshape(b, cap, nkv * hdv), qpos.reshape(b, s, 1),
      kpos.reshape(b, 1, cap))
    return out.reshape(b, s, nh, hdv)
