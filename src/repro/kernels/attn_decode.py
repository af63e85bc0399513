"""Fused quantized-KV decode attention Pallas kernels (GQA + MLA).

The decode regime mirrors :mod:`repro.kernels.lords_decode`: a handful of
query rows (the g = nh/nkv head-group per KV head for GQA, all nh heads for
MLA) against the full KV cache, so per-token cost is the time to *stream
the cache once*.  Both kernels walk the cache sequence axis innermost with
the flash-2 online-softmax recurrence and read the cache tiles **as
stored**: an int8 cache is DMA'd at int8 width and the per-(token, head)
scales are folded into the score / output dot-products in VMEM —

    score(g, j) = logit_scale · (q · codes_j) · k_scale_j
    out(g)     += (p ⊙ v_scale) · codes_v

— so dequantization adds one VPU multiply per tile instead of a full-cache
bf16 temporary in HBM (the reason the portable einsum path made int8 KV
*slower* than bf16 despite its ~2x bytes/token advantage).  A bf16 cache
runs the same kernels with the scale operands absent.

Layouts — the caches are indexed **in their stored layouts** via the
BlockSpec index maps (a host-side transpose would force XLA to copy the
entire cache every decode step, tripling the traffic the kernels exist to
minimize); only free reshapes are applied, so every block is lane-aligned:
  GQA:  q (b, nkv, g8, hd) · k/v (b, S, nkv, hd) [+ scales (b, S, nkv)],
        grid (b, nkv, S/bs) — one head-group per grid cell, q VMEM-resident,
        KV tiles (1, bs, hd) sliced from the (b, S, nkv·hd) view, and the
        (1, bs, nkv) scale tile narrowed to the cell's head in VMEM
  MLA:  q_lat (b, nh8, L) / q_rope (b, nh8, R) against the absorbed cache
        c (b, S, L) [+ c_scale (b, S)] and k_rope (b, S, R),
        grid (b, S/bs) — output *is* the weighted latent (b, nh8, L); the
        latent scale folds into the softmax weights (p ⊙ scale)·c

``kmask`` (b, S) f32 is the additive liveness mask (0 live / -1e30 dead),
viewed as (b, S/bs, 1, bs) so its tiles are whole rows:
positions beyond each sequence's ``pos`` and cache padding never
contribute, with the same finite-NEG_INF / alpha-correction NaN hygiene as
:mod:`repro.kernels.attn_prefill`.

Paged variants — the continuous-batching engine stores KV in a global pool
of fixed-size pages with a per-sequence page table ``pt`` (b, np) int32:
logical page ``pi`` of sequence ``b`` lives at physical page ``pt[b, pi]``.

  GQA:  the code pools stay in HBM as stored (P, ps, nkv, hd), viewed
        (P, ps·nkv, hd): rows in (token, head) order, a free reshape where
        the stored tile holds a token's heads whole (XLA relayouts an int8
        pool of fewer than 4 heads).  The scale pools (P, ps, nkv) are
        viewed (P, 1, ps·nkv) and padded to whole lane rows, one row per
        page: XLA relayouts both at every call, since Mosaic cannot copy a
        stored (ps, nkv) tile narrower than a lane row, and fetching those
        tiles through BlockSpecs instead costs the kernel more than the
        relayout.  A page's rows land in a VMEM slot of ``width`` rows,
        ps·nkv rounded up to 128, matching its scale row's lanes.
        ``pt`` and each slot's live length ``lens = pos + 1`` (at least 1)
        are scalar-prefetched.  Grid (b, ⌈np/ppb⌉): one step per slot and
        block of ``ppb`` pages (about 128 tokens).  A live block's live
        pages (index < ⌈len/ps⌉) are DMA'd page by page into a
        double-buffered VMEM scratch, and the copies of the next live block
        (this slot's next, or the next slot's first) start before this one
        computes; a block at or past the length starts no copy and does no
        work.  All KV heads of the slot go in one step: the block's
        (ppb·ps·nkv, hd) rows meet all nkv·g8 query rows in one score
        tile, and a static head mask keeps each query row to its own
        head's columns — so every page's rows and its scale row are read
        once, with the scales folded into the score and value weights.
        Liveness comes from an iota against ``lens``; dead columns (and
        rows of pages never copied) are masked, never read into the sums.
  MLA:  ``pt`` rides in the BlockSpec index maps (scalar prefetch), one
        page per grid step over the whole window, masked by ``kmask`` —
        the same kernel body as the contiguous MLA decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import ATTN_NEG_INF

__all__ = ["attn_decode_gqa_pallas", "attn_decode_mla_pallas",
           "attn_decode_gqa_paged_pallas", "attn_decode_mla_paged_pallas",
           "DECODE_ROWS"]

DECODE_ROWS = 8     # sublane multiple query rows are padded to
_STAT_LANES = 128


def _online_update(s, v, m_ref, l_ref, acc_ref, p_scale=None):
    """Shared flash-2 step: fold the (rows, bs) score tile ``s`` and value
    tile ``v`` into the running (m, l, acc) statistics.  ``p_scale``
    (1, bs) scales the weights of the value product only (a per-key value
    scale), never the normalizer."""
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_curr = jnp.max(s, axis=1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_curr)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next)
    l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_next, l_ref.shape)
    pv = p if p_scale is None else p * p_scale
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p


def _head_column(tile, head):
    """(bs, nkv) scale tile → the (bs, 1) column of kv head ``head``
    (a masked lane reduction: no dynamic lane slice)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.sum(jnp.where(lanes == head, tile, 0.0), axis=1, keepdims=True)


def _row_tiles(arr, bs: int):
    """(b, S) per-position row → (b, S/bs, 1, bs): each tile a whole row."""
    b, cap = arr.shape
    return arr.reshape(b, cap // bs, 1, bs)


def _gqa_kernel(q_ref, k_ref, v_ref, mask_ref, *rest, scale, nk, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    hi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, ATTN_NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # (g8, hd)
    k = k_ref[0].astype(jnp.float32)                         # (bs, hd)
    v = v_ref[0].astype(jnp.float32)                         # (bs, hdv)
    if quantized:  # per-(token, head) scales fold into the K / V rows
        k = k * _head_column(ks_ref[0], hi)
        v = v * _head_column(vs_ref[0], hi)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                        # (g8, bs)
    s = s + mask_ref[0, 0]                                   # (1, bs) additive
    _online_update(s, v, m_ref, l_ref, acc_ref)

    @pl.when(ki == nk - 1)
    def _store():
        l = l_ref[:, :1]
        inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
        o_ref[0, 0] = acc_ref[...] * inv


@functools.partial(
    jax.jit, static_argnames=("logit_scale", "bs", "interpret"))
def attn_decode_gqa_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kmask: jnp.ndarray,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    *,
    logit_scale: float,
    bs: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """q (b, nkv, g8, hd) vs cache k/v (b, S, nkv, hd) → (b, nkv, g8, hd_v).

    ``kmask`` (b, S) f32 additive liveness; ``k_scale``/``v_scale``
    (b, S, nkv) dequantize int8 caches in-kernel (pass both or neither).
    The cache operands keep the storage layout — the index maps slice
    per-head tiles, so no transposed copy of the cache ever exists.
    g8 must be a multiple of 8 and S of ``bs`` — the dispatch layer pads.
    """
    g8 = q.shape[2]
    cap = k.shape[1]
    bs = min(bs, cap)
    if cap % bs or g8 % DECODE_ROWS:
        raise ValueError(
            f"cache length {cap} % tile {bs} or rows {g8} % {DECODE_ROWS}")
    nk = cap // bs
    b, nkv, _, hd = q.shape
    hdv = v.shape[-1]
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    q_map = lambda bi, hi, ki: (bi, hi, 0, 0)  # noqa: E731
    kv_map = lambda bi, hi, ki: (bi, ki, hi)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, 1, g8, hd), q_map),
        pl.BlockSpec((1, bs, hd), kv_map),
        pl.BlockSpec((1, bs, hdv), kv_map),
        pl.BlockSpec((1, 1, 1, bs), lambda bi, hi, ki: (bi, ki, 0, 0)),
    ]
    args = [q, k.reshape(b, cap, nkv * hd), v.reshape(b, cap, nkv * hdv),
            _row_tiles(kmask, bs)]
    if quantized:
        in_specs += [pl.BlockSpec((1, bs, nkv),
                                  lambda bi, hi, ki: (bi, ki, 0))] * 2
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    kern = functools.partial(
        _gqa_kernel, scale=float(logit_scale), nk=nk, quantized=quantized)
    return pl.pallas_call(
        kern, grid=(b, nkv, nk), in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g8, hdv), q_map),
        out_shape=jax.ShapeDtypeStruct((b, nkv, g8, hdv), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g8, _STAT_LANES), jnp.float32),
            pltpu.VMEM((g8, _STAT_LANES), jnp.float32),
            pltpu.VMEM((g8, hdv), jnp.float32),
        ],
        interpret=interpret,
    )(*args)


def _mla_kernel(ql_ref, qr_ref, c_ref, kr_ref, mask_ref, *rest, scale, nk,
                quantized):
    if quantized:
        cs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, ATTN_NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ql = ql_ref[0].astype(jnp.float32)                       # (nh8, L)
    qr = qr_ref[0].astype(jnp.float32)                       # (nh8, R)
    c = c_ref[0].astype(jnp.float32)                         # (bs, L)
    kr = kr_ref[0].astype(jnp.float32)                       # (bs, R)
    s_lat = jax.lax.dot_general(
        ql, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                        # (nh8, bs)
    cs = cs_ref[0, 0] if quantized else None                 # (1, bs)
    if quantized:
        s_lat = s_lat * cs
    s = s_lat + jax.lax.dot_general(
        qr, kr, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    s = s * scale + mask_ref[0, 0]
    _online_update(s, c, m_ref, l_ref, acc_ref, p_scale=cs)

    @pl.when(ki == nk - 1)
    def _store():
        l = l_ref[:, :1]
        inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
        o_ref[0] = acc_ref[...] * inv


@functools.partial(
    jax.jit, static_argnames=("logit_scale", "bs", "interpret"))
def attn_decode_mla_pallas(
    q_lat: jnp.ndarray,
    q_rope: jnp.ndarray,
    c: jnp.ndarray,
    k_rope: jnp.ndarray,
    kmask: jnp.ndarray,
    c_scale: jnp.ndarray | None = None,
    *,
    logit_scale: float,
    bs: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Absorbed-latent MLA decode: q_lat (b, nh8, L) / q_rope (b, nh8, R)
    vs c (b, S, L) + k_rope (b, S, R) → weighted latent (b, nh8, L) f32.

    ``c_scale`` (b, S) dequantizes an int8 latent cache in-kernel.
    """
    b, nh8, lat = q_lat.shape
    cap = c.shape[1]
    rope = q_rope.shape[-1]
    quantized = c_scale is not None
    bs = min(bs, cap)
    if cap % bs or nh8 % DECODE_ROWS:
        raise ValueError(
            f"cache length {cap} % tile {bs} or rows {nh8} % {DECODE_ROWS}")
    nk = cap // bs
    grid = (b, nk)

    in_specs = [
        pl.BlockSpec((1, nh8, lat), lambda bi, ki: (bi, 0, 0)),
        pl.BlockSpec((1, nh8, rope), lambda bi, ki: (bi, 0, 0)),
        pl.BlockSpec((1, bs, lat), lambda bi, ki: (bi, ki, 0)),
        pl.BlockSpec((1, bs, rope), lambda bi, ki: (bi, ki, 0)),
        pl.BlockSpec((1, 1, 1, bs), lambda bi, ki: (bi, ki, 0, 0)),
    ]
    args = [q_lat, q_rope, c, k_rope, _row_tiles(kmask, bs)]
    if quantized:
        in_specs.append(
            pl.BlockSpec((1, 1, 1, bs), lambda bi, ki: (bi, ki, 0, 0)))
        args.append(_row_tiles(c_scale.astype(jnp.float32), bs))

    kern = functools.partial(
        _mla_kernel, scale=float(logit_scale), nk=nk, quantized=quantized)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh8, lat), lambda bi, ki: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nh8, lat), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((nh8, _STAT_LANES), jnp.float32),
            pltpu.VMEM((nh8, _STAT_LANES), jnp.float32),
            pltpu.VMEM((nh8, lat), jnp.float32),
        ],
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------------
# Block-paged variants: KV tiles indexed through the page map
# ---------------------------------------------------------------------------


_BLOCK_TOKENS = 128  # tokens a paged GQA grid step covers (whole pages)


def _gqa_paged_kernel(lens_ref, pt_ref, q_ref, k_hbm, v_hbm, *rest, scale,
                      ppb, npages, quantized):
    if quantized:
        (ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem, buf_ref,
         m_ref, l_ref, acc_ref) = rest
        scales = ((ks_hbm, ksbuf), (vs_hbm, vsbuf))
    else:
        o_ref, kbuf, vbuf, sem, buf_ref, m_ref, l_ref, acc_ref = rest
        scales = ()
    bi, ki = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(0)
    nkv, g8, hd = q_ref.shape[1:]
    hdv = vbuf.shape[-1]
    width = kbuf.shape[2]                # a page's rows, padded to 128
    ps = k_hbm.shape[1] // nkv
    rows, cols = nkv * g8, ppb * width

    def live_pages(b):
        return jnp.minimum((lens_ref[b] + ps - 1) // ps, npages)

    def block_copies(b, blk, slot, fn):
        """``fn`` on the DMA of every live page of block ``blk`` of slot
        ``b`` (codes, and scales as one row) into buffer ``slot``."""
        def body(j, carry):
            page = pt_ref[b * npages + blk * ppb + j]
            for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf)):
                fn(pltpu.make_async_copy(
                    src.at[page], dst.at[slot, j, pl.ds(0, ps * nkv)],
                    sem.at[slot]))
            for src, dst in scales:
                fn(pltpu.make_async_copy(src.at[page], dst.at[slot, j],
                                         sem.at[slot]))
            return carry
        jax.lax.fori_loop(
            0, jnp.minimum(ppb, live_pages(b) - blk * ppb), body, 0)

    @pl.when((bi == 0) & (ki == 0))
    def _prime():
        buf_ref[0] = 0
        block_copies(0, 0, 0, lambda c: c.start())

    npl = live_pages(bi)

    @pl.when(ki * ppb < npl)
    def _block():
        slot = buf_ref[0]
        more = (ki + 1) * ppb < npl

        @pl.when(more | (bi + 1 < nb))
        def _prefetch():  # this slot's next block, or the next slot's first
            block_copies(jnp.where(more, bi, bi + 1),
                         jnp.where(more, ki + 1, 0), 1 - slot,
                         lambda c: c.start())
            buf_ref[0] = 1 - slot

        @pl.when(ki == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, ATTN_NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        block_copies(bi, ki, slot, lambda c: c.wait())
        left = lens_ref[bi] - ki * ppb * ps     # live tokens from here on

        def is_live(c):  # column / row c: page c // width, (token, head)
            lane = c % width
            return (lane < ps * nkv) & ((c // width) * ps + lane // nkv
                                         < left)

        live = is_live(jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1))
        # query row h·g8 + i scores only the columns (token, h) of its head
        mine = (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) % nkv
                == jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) // g8)
        q = q_ref[0].astype(jnp.float32).reshape(rows, hd) * scale
        k = kbuf[slot].astype(jnp.float32).reshape(cols, hd)
        v = vbuf[slot].astype(jnp.float32).reshape(cols, hdv)
        # rows never copied (padding, later pages) hold stale VMEM: zero them
        v = jnp.where(is_live(jax.lax.broadcasted_iota(jnp.int32, (cols, 1),
                                                       0)), v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        v_scale = None
        if quantized:  # per-(token, head) scales, one row per page
            s = s * jnp.concatenate([ksbuf[slot, j] for j in range(ppb)], 1)
            v_scale = jnp.where(live, jnp.concatenate(
                [vsbuf[slot, j] for j in range(ppb)], 1), 0.0)
        s = jnp.where(mine & live, s, ATTN_NEG_INF)
        _online_update(s, v, m_ref, l_ref, acc_ref, p_scale=v_scale)

        @pl.when(ki == (npl + ppb - 1) // ppb - 1)
        def _store():
            l = l_ref[:, :1]
            inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
            o_ref[0] = (acc_ref[...] * inv).reshape(nkv, g8, hdv)


@functools.partial(jax.jit, static_argnames=("logit_scale", "interpret"))
def attn_decode_gqa_paged_pallas(
    pt: jnp.ndarray,
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    lens: jnp.ndarray,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    *,
    logit_scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged GQA decode: q (b, nkv, g8, hd) vs a page pool.

    ``pt`` (b, np) int32 maps logical page ``pi`` of sequence ``b`` to its
    physical page in ``k_pool``/``v_pool`` (P, ps, nkv, hd) [+ scale pools
    (P, ps, nkv)].  ``lens`` (b,) int32 is each sequence's live length
    (``pos + 1``, at least 1): only the pages it covers are read.  Grid
    (b, ⌈np/ppb⌉) with ``ppb`` pages of about 128 tokens a step; see the
    module docstring.  Returns (b, nkv, g8, hd_v) f32.
    """
    b, nkv, g8, hd = q.shape
    n_pool, ps, _, hdv = v_pool.shape
    npages = pt.shape[1]
    if ps % 8 or g8 % DECODE_ROWS:
        raise ValueError(
            f"page size {ps} % 8 or rows {g8} % {DECODE_ROWS}")
    if lens.shape != (b,):
        raise ValueError(f"lens {lens.shape} != (b,) = {(b,)}")
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    ppb = max(1, min(npages, _BLOCK_TOKENS // ps))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    slot_map = lambda bi, ki, *_: (bi, 0, 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, nkv, g8, hd), slot_map), pool_spec,
                pool_spec]
    # a page's codes as ps·nkv rows in (token, head) order, each copied into
    # a slot of ``width`` rows; its scales as one row of ``width`` lanes
    width = -(-ps * nkv // 128) * 128
    args = [q, k_pool.reshape(n_pool, ps * nkv, hd),
            v_pool.reshape(n_pool, ps * nkv, hdv)]
    scratch = [pltpu.VMEM((2, ppb, width, hd), k_pool.dtype),
               pltpu.VMEM((2, ppb, width, hdv), v_pool.dtype)]
    if quantized:
        in_specs += [pool_spec] * 2
        args += [jnp.pad(sc.astype(jnp.float32).reshape(-1, 1, ps * nkv),
                         ((0, 0), (0, 0), (0, width - ps * nkv)))
                 for sc in (k_scale, v_scale)]
        scratch += [pltpu.VMEM((2, ppb, 1, width), jnp.float32)] * 2
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SMEM((1,), jnp.int32),     # the buffer the next block is in
        pltpu.VMEM((nkv * g8, _STAT_LANES), jnp.float32),
        pltpu.VMEM((nkv * g8, _STAT_LANES), jnp.float32),
        pltpu.VMEM((nkv * g8, hdv), jnp.float32),
    ]
    kern = functools.partial(
        _gqa_paged_kernel, scale=float(logit_scale), ppb=ppb, npages=npages,
        quantized=quantized)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, pl.cdiv(npages, ppb)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, nkv, g8, hdv), slot_map),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, nkv, g8, hdv), jnp.float32),
        # steps run in order: each one starts the next live block's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(lens.astype(jnp.int32), pt.reshape(-1), *args)


@functools.partial(jax.jit, static_argnames=("logit_scale", "interpret"))
def attn_decode_mla_paged_pallas(
    pt: jnp.ndarray,
    q_lat: jnp.ndarray,
    q_rope: jnp.ndarray,
    c_pool: jnp.ndarray,
    k_rope_pool: jnp.ndarray,
    kmask: jnp.ndarray,
    c_scale: jnp.ndarray | None = None,
    *,
    logit_scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged absorbed-latent MLA decode: q_lat (b, nh8, L) / q_rope
    (b, nh8, R) vs c_pool (P, ps, L) + k_rope_pool (P, ps, R) [+ c_scale
    pool (P, ps)] through ``pt`` (b, np); kmask (b, np*ps).  Same kernel
    body as the contiguous MLA decode; returns the weighted latent
    (b, nh8, L) f32."""
    b, nh8, lat = q_lat.shape
    ps = c_pool.shape[1]
    rope = q_rope.shape[-1]
    npages = pt.shape[1]
    quantized = c_scale is not None
    if ps % 8 or nh8 % DECODE_ROWS:
        raise ValueError(
            f"page size {ps} % 8 or rows {nh8} % {DECODE_ROWS}")
    if kmask.shape != (b, npages * ps):
        raise ValueError(
            f"kmask {kmask.shape} != (b, np*ps) = {(b, npages * ps)}")
    grid = (b, npages)

    in_specs = [
        pl.BlockSpec((1, nh8, lat), lambda bi, ki, pt_ref: (bi, 0, 0)),
        pl.BlockSpec((1, nh8, rope), lambda bi, ki, pt_ref: (bi, 0, 0)),
        pl.BlockSpec((1, ps, lat),
                     lambda bi, ki, pt_ref: (pt_ref[bi, ki], 0, 0)),
        pl.BlockSpec((1, ps, rope),
                     lambda bi, ki, pt_ref: (pt_ref[bi, ki], 0, 0)),
        pl.BlockSpec((1, 1, 1, ps), lambda bi, ki, pt_ref: (bi, ki, 0, 0)),
    ]
    args = [q_lat, q_rope, c_pool, k_rope_pool, _row_tiles(kmask, ps)]
    if quantized:
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, ps), lambda bi, ki, pt_ref: (pt_ref[bi, ki], 0, 0, 0)))
        args.append(c_scale.astype(jnp.float32).reshape(-1, 1, 1, ps))

    body = functools.partial(
        _mla_kernel, scale=float(logit_scale), nk=npages,
        quantized=quantized)

    def kern(pt_ref, *refs):
        del pt_ref
        body(*refs)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh8, lat),
                               lambda bi, ki, pt_ref: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh8, _STAT_LANES), jnp.float32),
            pltpu.VMEM((nh8, _STAT_LANES), jnp.float32),
            pltpu.VMEM((nh8, lat), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh8, lat), jnp.float32),
        interpret=interpret,
    )(pt, *args)
