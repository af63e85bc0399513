"""Attention mixers: GQA/MQA (chunked-causal) and MLA (latent KV compression).

Pure-JAX implementations built for three regimes:
  * train/prefill — q-chunked causal attention (flash-style memory profile:
    the (seq × seq) score matrix never materializes; peak extra memory is
    (batch, heads, chunk, seq) per layer, rematerialized in backward),
  * decode — single-token query against a fixed-capacity KV cache,
  * MLA decode uses the *absorbed* latent form: the cache stores the
    compressed c_kv + shared RoPE key only (kv_lora + rope floats per token
    instead of 2·nh·hd) — the paper-native cache-compression win.

All linear projections (fused QKV/O, MLA down/up) go through the unified
kernel-dispatch layer (:func:`repro.kernels.dispatch.qmatmul`), so LoRDS /
any baseline runs its fused dequant-matmul on TPU and its oracle elsewhere.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import fused_backend_active, qattention, qmatmul
from repro.models.common import (
    P,
    apply_rope,
    f32_einsum,
    kv_dequantize,
    kv_quantize,
    qlinear_init,
    rmsnorm,
    rmsnorm_init,
    rope_attn_scale,
    shard,
)

__all__ = [
    "gqa_init", "gqa_train", "gqa_decode",
    "mla_init", "mla_train", "mla_decode",
    "gqa_cache_init", "mla_cache_init",
    "gqa_paged_cache_init", "mla_paged_cache_init",
    "gqa_decode_paged", "mla_decode_paged",
    "gqa_prefill_chunk", "mla_prefill_chunk",
]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# shared chunked causal core
# ---------------------------------------------------------------------------


def chunked_causal_attention(q, k, v, *, chunk=512, logit_scale=None,
                             positions=None):
    """q (b,s,nh,hd), k/v (b,s,nkv,hd) -> (b,s,nh,hd); causal.

    On the fused backends (pallas/interpret) this routes through
    ``dispatch.qattention("prefill", ...)`` — the streaming-softmax flash
    kernel that reads the *unexpanded* GQA KV heads and never materializes
    a score matrix.  The chunked einsum body below is the portable path
    and the fused kernel's parity oracle.

    ``positions`` (b, s) int32 drives the causal mask (ragged / shifted
    sequences mask per batch row; -1 marks dead padding rows); None means
    the standard aligned arange.

    Ref-path notes: GQA keys/values are expanded to the full head count
    *before* the score einsum — a (nkv, g) reshape of a TP-sharded head
    dim is not representable in GSPMD and silently replicates the
    (b,h,chunk,s) score tensors, while the expansion keeps everything
    head-sharded (the flash kernel avoids the expansion natively via its
    KV index map).  The chunk body is rematerialized: backward keeps only
    (q-chunk, out).
    """
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    scale = logit_scale if logit_scale is not None else 1.0 / math.sqrt(hd)
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    if fused_backend_active():
        out = qattention("prefill", q, k, v, positions,
                         logit_scale=float(scale))
        return out.astype(q.dtype)

    chunk = min(chunk, s)
    if s % chunk:  # odd smoke-test lengths: fall back to a divisor
        chunk = math.gcd(chunk, s) or s
    nc = s // chunk

    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        k = shard(k, "batch", "seq", "heads", "head_dim")
        v = shard(v, "batch", "seq", "heads", "head_dim")
    kpos = positions  # (b, s)

    def body(carry, inputs):
        qc, ci = inputs  # (b, chunk, nh, hd), scalar chunk index
        qpos = jax.lax.dynamic_slice_in_dim(positions, ci * chunk, chunk,
                                            axis=1)          # (b, chunk)
        scores = f32_einsum(
            "bcnh,bsnh->bncs", qc * jnp.asarray(scale, qc.dtype), k)
        mask = (kpos[:, None, :] <= qpos[:, :, None]) \
            & (kpos[:, None, :] >= 0)                        # (b, chunk, s)
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = f32_einsum("bncs,bsnh->bcnh", probs, v)
        return carry, out.astype(q.dtype)

    qc_stack = jnp.moveaxis(q.reshape(b, nc, chunk, nh, hd), 1, 0)
    _, outs = jax.lax.scan(
        jax.checkpoint(body),
        None, (qc_stack, jnp.arange(nc, dtype=jnp.int32))
    )
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, nh, v.shape[-1])
    return out.astype(q.dtype)


def _scatter_token(cache_arr, new, pos):
    """Write per-sequence entries ``new`` (b, 1, ...) into ``cache_arr``
    (b, S, ...) at per-sequence positions ``pos`` (b,) int32.

    Ragged-safe: each batch row scatters at its own position (the old code
    used pos[0] for the whole batch, silently corrupting ragged batches).
    """
    return jax.vmap(
        lambda c, u, p: jax.lax.dynamic_update_slice(
            c, u, (p,) + (0,) * (c.ndim - 1))
    )(cache_arr, new, pos)


def decode_attention(q, k_cache, v_cache, pos, *, logit_scale=None,
                     k_scale=None, v_scale=None):
    """q (b,1,nh,hd) vs cache (b,S,nkv,hd); positions<=pos are live.

    With ``k_scale``/``v_scale`` (b,S,nkv) the caches hold per-head int8
    codes.  On the fused backends the whole read side routes through
    ``dispatch.qattention("decode", ...)`` — the cache streams through the
    flash-decode kernel once, *as stored*, with the per-(token, head)
    scales folded into the in-kernel dot products: int8 KV pays int8
    bandwidth (the full roofline number bench_serve reports).  The einsum
    body below is the portable path / parity oracle; it dequantizes the
    entire cache up front, which is why int8 used to *lose* to bf16 here.
    """
    b, _, nh, hd = q.shape
    nkv = k_cache.shape[2]
    g = nh // nkv
    cap = k_cache.shape[1]
    scale = logit_scale if logit_scale is not None else 1.0 / math.sqrt(hd)
    if fused_backend_active():
        out = qattention("decode", q[:, 0], k_cache, v_cache, pos,
                         k_scale, v_scale, logit_scale=float(scale))
        return out[:, None].astype(q.dtype)  # (b, 1, nh, hd_v)
    if k_scale is not None:
        k_cache = kv_dequantize(k_cache, k_scale, dtype=q.dtype)
    if v_scale is not None:
        v_cache = kv_dequantize(v_cache, v_scale, dtype=q.dtype)
    qg = q.reshape(b, nkv, g, hd)
    scores = f32_einsum(
        "bngh,bsnh->bngs", qg * jnp.asarray(scale, qg.dtype), k_cache)
    live = jnp.arange(cap, dtype=jnp.int32)[None, :] <= pos[:, None]  # (b,S)
    scores = jnp.where(live[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = f32_einsum("bngs,bsnh->bngh", probs, v_cache)
    return out.reshape(b, 1, nh, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA / MQA
# ---------------------------------------------------------------------------


def gqa_init(key, cfg, quant):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": qlinear_init(ks[0], nh * hd, d, quant, "qkv_out", "embed"),
        "wk": qlinear_init(ks[1], nkv * hd, d, quant, "kv_out", "embed"),
        "wv": qlinear_init(ks[2], nkv * hd, d, quant, "kv_out", "embed"),
        "wo": qlinear_init(ks[3], d, nh * hd, quant, "embed", "qkv_out"),
    }


def _gqa_qkv(params, x, cfg, quant, positions):
    b, s, d = x.shape
    hd, nh, nkv = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    q = qmatmul(params["wq"], x, quant, nh * hd, d).reshape(b, s, nh, hd)
    k = qmatmul(params["wk"], x, quant, nkv * hd, d).reshape(b, s, nkv, hd)
    v = qmatmul(params["wv"], x, quant, nkv * hd, d).reshape(b, s, nkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def gqa_train(params, x, cfg, quant, positions, chunk=512):
    b, s, d = x.shape
    nh, hd = cfg.num_heads, cfg.resolved_head_dim
    q, k, v = _gqa_qkv(params, x, cfg, quant, positions)
    out = chunked_causal_attention(q, k, v, chunk=chunk,
                                   positions=positions)
    out = out.reshape(b, s, nh * hd)
    return qmatmul(params["wo"], out, quant, d, nh * hd)


def gqa_cache_init(cfg, batch, capacity, dtype=jnp.bfloat16):
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    shape = (batch, capacity, nkv, hd)
    axes = ("batch", "cache_seq", "kv_heads", "head_dim")
    if cfg.kv_cache_dtype == "int8":
        s_axes = ("batch", "cache_seq", "kv_heads")
        return {
            "k": P(jnp.zeros(shape, jnp.int8), axes),
            "v": P(jnp.zeros(shape, jnp.int8), axes),
            "k_scale": P(jnp.zeros(shape[:3], jnp.float32), s_axes),
            "v_scale": P(jnp.zeros(shape[:3], jnp.float32), s_axes),
        }
    return {"k": P(jnp.zeros(shape, dtype), axes),
            "v": P(jnp.zeros(shape, dtype), axes)}


def _kv_store(cache, name, new, pos=None):
    """Store ``new`` (b, s, nkv, hd) into cache slot ``name``, quantizing to
    the cache's storage format.  pos None = prefill (write at 0); pos (b,)
    = decode (ragged per-sequence scatter)."""
    quantized = f"{name}_scale" in cache
    if quantized:
        codes, scale = kv_quantize(new)
        if pos is None:
            out = {
                name: jax.lax.dynamic_update_slice(
                    cache[name], codes, (0,) * cache[name].ndim),
                f"{name}_scale": jax.lax.dynamic_update_slice(
                    cache[f"{name}_scale"], scale,
                    (0,) * cache[f"{name}_scale"].ndim),
            }
        else:
            out = {
                name: _scatter_token(cache[name], codes, pos),
                f"{name}_scale": _scatter_token(
                    cache[f"{name}_scale"], scale, pos),
            }
    elif pos is None:
        out = {name: jax.lax.dynamic_update_slice(
            cache[name], new.astype(cache[name].dtype),
            (0,) * cache[name].ndim)}
    else:
        out = {name: _scatter_token(
            cache[name], new.astype(cache[name].dtype), pos)}
    return out


def gqa_prefill(params, x, cfg, quant, positions, cache, chunk=512):
    """Train-style forward that also fills the cache (capacity == seq)."""
    b, s, d = x.shape
    nh, hd = cfg.num_heads, cfg.resolved_head_dim
    q, k, v = _gqa_qkv(params, x, cfg, quant, positions)
    out = chunked_causal_attention(q, k, v, chunk=chunk,
                                   positions=positions)
    out = out.reshape(b, s, nh * hd)
    new_cache = {**_kv_store(cache, "k", k), **_kv_store(cache, "v", v)}
    return qmatmul(params["wo"], out, quant, d, nh * hd), new_cache


def gqa_decode(params, x, cfg, quant, cache, pos):
    """x (b,1,d); pos (b,) current position; cache dict of (b,S,nkv,hd).

    Positions may be ragged (one per sequence): the new KV scatters at each
    sequence's own slot and the attention mask is already per-sequence.
    """
    b, _, d = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = qmatmul(params["wq"], x, quant, nh * hd, d).reshape(b, 1, nh, hd)
    k = qmatmul(params["wk"], x, quant, nkv * hd, d).reshape(b, 1, nkv, hd)
    v = qmatmul(params["wv"], x, quant, nkv * hd, d).reshape(b, 1, nkv, hd)
    q = apply_rope(q, pos[:, None], cfg.rope_theta, cfg.rope_scaling)
    k = apply_rope(k, pos[:, None], cfg.rope_theta, cfg.rope_scaling)
    new_cache = {**_kv_store(cache, "k", k, pos),
                 **_kv_store(cache, "v", v, pos)}
    new_cache = {
        kk: shard(vv, "batch", "cache_seq", "kv_heads", "head_dim"
                  ) if vv.ndim == 4
        else shard(vv, "batch", "cache_seq", "kv_heads")
        for kk, vv in new_cache.items()
    }
    out = decode_attention(q, new_cache["k"], new_cache["v"], pos,
                           k_scale=new_cache.get("k_scale"),
                           v_scale=new_cache.get("v_scale"))
    out = out.reshape(b, 1, nh * hd)
    y = qmatmul(params["wo"], out, quant, d, nh * hd)
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-style multi-head latent attention; minicpm3)
# ---------------------------------------------------------------------------


def mla_init(key, cfg, quant):
    """Latent attention's linears.  q comes through a LoRA (``q_down``,
    ``q_norm``, ``q_up``) or, where ``q_lora_rank`` is None, from one
    direct projection ``wq``."""
    m, d, nh = cfg.mla, cfg.d_model, cfg.num_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 6)
    if m.q_lora_rank is None:
        q = {"wq": qlinear_init(ks[0], nh * qk, d, quant, "qkv_out",
                                "embed")}
    else:
        q = {"q_down": qlinear_init(ks[0], m.q_lora_rank, d, quant,
                                    "q_lora", "embed"),
             "q_up": qlinear_init(ks[1], nh * qk, m.q_lora_rank, quant,
                                  "qkv_out", "q_lora"),
             "q_norm": rmsnorm_init(m.q_lora_rank, "q_lora")}
    return {
        **q,
        "kv_down": qlinear_init(
            ks[2], m.kv_lora_rank + m.qk_rope_dim, d, quant, "kv_lora", "embed"),
        "k_up": qlinear_init(
            ks[3], nh * m.qk_nope_dim, m.kv_lora_rank, quant, "qkv_out", "kv_lora"),
        "v_up": qlinear_init(
            ks[4], nh * m.v_head_dim, m.kv_lora_rank, quant, "qkv_out", "kv_lora"),
        "wo": qlinear_init(ks[5], d, nh * m.v_head_dim, quant, "embed", "qkv_out"),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, "kv_lora"),
    }


def _mla_q(params, x, cfg, quant, positions):
    m, d, nh = cfg.mla, cfg.d_model, cfg.num_heads
    b, s, _ = x.shape
    qk = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora_rank is None:
        q = qmatmul(params["wq"], x, quant, nh * qk, d)
    else:
        ql = qmatmul(params["q_down"], x, quant, m.q_lora_rank, d)
        ql = rmsnorm(params["q_norm"], ql, cfg.norm_eps)
        q = qmatmul(params["q_up"], ql, quant, nh * qk, m.q_lora_rank)
    q = q.reshape(b, s, nh, qk)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta,
                        cfg.rope_scaling)
    return q_nope, q_rope


def _mla_scale(cfg) -> float:
    """Softmax scale of latent attention: 1/sqrt(nope + rope), times
    YaRN's mscale² where the config scales its rope."""
    m = cfg.mla
    return rope_attn_scale(m.qk_nope_dim + m.qk_rope_dim, cfg.rope_scaling)


def _mla_latents(params, x, cfg, quant, positions):
    m, d = cfg.mla, cfg.d_model
    ckv = qmatmul(
        params["kv_down"], x, quant, m.kv_lora_rank + m.qk_rope_dim, d)
    c, k_rope = ckv[..., : m.kv_lora_rank], ckv[..., m.kv_lora_rank:]
    c = rmsnorm(params["kv_norm"], c, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        cfg.rope_scaling)[:, :, 0]
    return c, k_rope  # (b,s,kv_lora), (b,s,rope)


def mla_train(params, x, cfg, quant, positions, chunk=512):
    m, d, nh = cfg.mla, cfg.d_model, cfg.num_heads
    b, s, _ = x.shape
    q_nope, q_rope = _mla_q(params, x, cfg, quant, positions)
    c, k_rope = _mla_latents(params, x, cfg, quant, positions)
    k_nope = qmatmul(
        params["k_up"], c, quant, nh * m.qk_nope_dim, m.kv_lora_rank
    ).reshape(b, s, nh, m.qk_nope_dim)
    v = qmatmul(
        params["v_up"], c, quant, nh * m.v_head_dim, m.kv_lora_rank
    ).reshape(b, s, nh, m.v_head_dim)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], (b, s, nh, m.qk_rope_dim))],
        axis=-1,
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = _mla_scale(cfg)
    out = chunked_causal_attention(q, k, v, chunk=chunk, logit_scale=scale,
                                   positions=positions)
    out = out.reshape(b, s, nh * m.v_head_dim)
    return qmatmul(params["wo"], out, quant, d, nh * m.v_head_dim)


def mla_cache_init(cfg, batch, capacity, dtype=jnp.bfloat16):
    m = cfg.mla
    cache = {
        "c": P(jnp.zeros((batch, capacity, m.kv_lora_rank), dtype),
               ("batch", "cache_seq", "kv_lora")),
        "k_rope": P(jnp.zeros((batch, capacity, m.qk_rope_dim), dtype),
                    ("batch", "cache_seq", "rope_dim")),
    }
    if cfg.kv_cache_dtype == "int8":
        # quantize the compressed latent (the bulk of the MLA cache);
        # k_rope is qk_rope_dim floats/token — not worth a scale per row
        cache["c"] = P(
            jnp.zeros((batch, capacity, m.kv_lora_rank), jnp.int8),
            ("batch", "cache_seq", "kv_lora"))
        cache["c_scale"] = P(jnp.zeros((batch, capacity), jnp.float32),
                             ("batch", "cache_seq"))
    return cache


def mla_prefill(params, x, cfg, quant, positions, cache, chunk=512):
    y = mla_train(params, x, cfg, quant, positions, chunk=chunk)
    c, k_rope = _mla_latents(params, x, cfg, quant, positions)
    new_cache = {**_kv_store(cache, "c", c),
                 **_kv_store(cache, "k_rope", k_rope)}
    return y, new_cache


def mla_decode(params, x, cfg, quant, cache, pos):
    """Absorbed-latent decode: cache is (c, k_rope) only; pos may be ragged.

    With an int8 latent cache the dequant happens right here at the two
    latent einsums; as in :func:`decode_attention`, the footprint saving is
    structural while the traffic saving depends on the dequant fusing into
    the einsum reads (fused-kernel target: int8 codes + one f32 scale per
    token).
    """
    m, d, nh = cfg.mla, cfg.d_model, cfg.num_heads
    b = x.shape[0]
    q_nope, q_rope = _mla_q(params, x, cfg, quant, pos[:, None])
    c_new, k_rope_new = _mla_latents(params, x, cfg, quant, pos[:, None])
    new_cache = {**_kv_store(cache, "c", c_new, pos),
                 **_kv_store(cache, "k_rope", k_rope_new, pos)}
    r_cache = new_cache["k_rope"]
    scale = _mla_scale(cfg)

    # absorb k_up into q:  q_lat (b,1,nh,kv_lora)
    w_kup = _dequant(params["k_up"], cfg, quant, nh * m.qk_nope_dim, m.kv_lora_rank)
    w_kup = w_kup.reshape(nh, m.qk_nope_dim, m.kv_lora_rank)
    q_lat = f32_einsum("bthn,hnl->bthl", q_nope, w_kup.astype(q_nope.dtype))

    if fused_backend_active():
        # fused path: the (possibly int8) latent cache streams through the
        # flash-decode kernel once, as stored — no full-cache dequant temp
        lat = qattention(
            "mla_decode", q_lat[:, 0], q_rope[:, 0], new_cache["c"],
            r_cache, pos, new_cache.get("c_scale"),
            logit_scale=scale)[:, None]
    else:
        if "c_scale" in new_cache:
            c_cache = kv_dequantize(new_cache["c"], new_cache["c_scale"],
                                    dtype=r_cache.dtype)
        else:
            c_cache = new_cache["c"]
        cap = c_cache.shape[1]
        scores = f32_einsum("bthl,bsl->bhts", q_lat.astype(c_cache.dtype),
                            c_cache)
        scores += f32_einsum("bthr,bsr->bhts", q_rope.astype(r_cache.dtype),
                             r_cache)
        scores *= scale
        live = jnp.arange(cap, dtype=jnp.int32)[None, :] <= pos[:, None]
        scores = jnp.where(live[:, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(c_cache.dtype)
        lat = f32_einsum("bhts,bsl->bthl", probs, c_cache)
    w_vup = _dequant(params["v_up"], cfg, quant, nh * m.v_head_dim, m.kv_lora_rank)
    w_vup = w_vup.reshape(nh, m.v_head_dim, m.kv_lora_rank)
    out = f32_einsum("bthl,hvl->bthv", lat.astype(w_vup.dtype), w_vup)
    out = out.reshape(b, 1, nh * m.v_head_dim).astype(x.dtype)
    y = qmatmul(params["wo"], out, quant, d, nh * m.v_head_dim)
    return y, new_cache


def _dequant(ptree, cfg, quant, n, mdim):
    from repro.core import dequantize_weight

    return dequantize_weight(ptree, quant, n, mdim)


# ---------------------------------------------------------------------------
# block-paged KV (continuous-batching serving)
# ---------------------------------------------------------------------------
#
# The paged cache replaces the per-sequence (b, S, ...) cache with a global
# pool (P, ps, ...) of fixed-size pages plus a per-sequence page table
# (b, np) int32: logical page pi of slot b lives at physical page pt[b, pi].
# Page 0 is reserved as a dummy/scratch page — the engine points every
# unallocated (or inactive-slot) table entry at it, so fixed-shape decode
# steps can always run the full batch: dead slots scatter into page 0 and
# their reads are masked.  Decode reads go through
# qattention("paged_decode"/"paged_mla_decode") — the page table rides into
# the Pallas index maps, the int8 pool streams once as stored (the ref
# backend gathers; that's the jaxpr-guard negative control, not the serving
# path).  Writes are scatters into the flattened pool — never a gather.


def _paged_scatter_token(pool_arr, new, pt, pos):
    """Scatter per-sequence entries ``new`` (b, 1, ...) into the pool
    (P, ps, ...) at each slot's current position through the page table."""
    P_, ps = pool_arr.shape[:2]
    flat = pool_arr.reshape((P_ * ps,) + pool_arr.shape[2:])
    page = jnp.take_along_axis(pt, (pos // ps)[:, None], axis=1)[:, 0]
    idx = page * ps + pos % ps                                     # (b,)
    flat = flat.at[idx].set(new[:, 0].astype(pool_arr.dtype))
    return flat.reshape(pool_arr.shape)


def _paged_scatter_chunk(pool_arr, new, pt, pos0):
    """Write a prefill chunk ``new`` (b, cs, ...) as whole pages.

    Requires cs % ps == 0 and pos0 % ps == 0 (the engine aligns its chunk
    size to the page size), so the chunk covers cs/ps full pages per slot
    and the write is a page-granular scatter.  Rows past a slot's prompt
    carry garbage (dead qpos) — they land in pages that decode either masks
    (beyond pos) or overwrites token-by-token as pos advances."""
    b, cs = new.shape[:2]
    ps = pool_arr.shape[1]
    npg = cs // ps
    tiles = new.reshape((b * npg, ps) + new.shape[2:])
    lp = pos0[:, None] // ps + jnp.arange(npg, dtype=pt.dtype)[None]
    phys = jnp.take_along_axis(pt, lp, axis=1).reshape(-1)     # (b*npg,)
    return pool_arr.at[phys].set(tiles.astype(pool_arr.dtype))


def _paged_store(pool, name, new, pt, pos=None, pos0=None):
    """Paged analogue of :func:`_kv_store`: quantize ``new`` to the pool's
    storage format and scatter it through the page table.  Exactly one of
    ``pos`` (b,) (single-token decode write) / ``pos0`` (b,) (page-aligned
    chunk write) must be given.  Named scope ``kv_store``."""
    scatter = (functools.partial(_paged_scatter_token, pt=pt, pos=pos)
               if pos is not None
               else functools.partial(_paged_scatter_chunk, pt=pt,
                                      pos0=pos0))
    with jax.named_scope("kv_store"):
        if f"{name}_scale" in pool:
            codes, scale = kv_quantize(new)
            return {name: scatter(pool[name], codes),
                    f"{name}_scale": scatter(pool[f"{name}_scale"], scale)}
        return {name: scatter(pool[name], new)}


def _paged_window(pool, name, pt, dtype):
    """Gather + dequantize the full logical window (b, np*ps, ...) of slot
    ``name`` — the *prefix* read of chunked prefill (a chunk's queries
    attend to everything earlier sequences of chunks wrote).  Decode never
    calls this: its reads go through the paged kernels.  Named scope
    ``kv_window``."""
    arr = pool[name]
    P_, ps = arr.shape[:2]
    b = pt.shape[0]
    with jax.named_scope("kv_window"):
        flat = arr.reshape((P_ * ps,) + arr.shape[2:])
        idx = (pt[:, :, None] * ps
               + jnp.arange(ps, dtype=pt.dtype)[None, None]).reshape(b, -1)
        win = jnp.take(flat, idx, axis=0)               # (b, np*ps, ...)
        if f"{name}_scale" in pool:
            sarr = pool[f"{name}_scale"]
            swin = jnp.take(sarr.reshape((P_ * ps,) + sarr.shape[2:]), idx,
                            axis=0)
            return kv_dequantize(win, swin, dtype=dtype)
        return win.astype(dtype)


def gqa_paged_cache_init(cfg, total_pages, page_size, dtype=jnp.bfloat16):
    """Global page pool: (P, ps, nkv, hd) [+ scale pools (P, ps, nkv)].

    Pages never shard over data (every slot shares the pool); the kv_heads
    dim keeps the same model-axis rule as the contiguous cache."""
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    shape = (total_pages, page_size, nkv, hd)
    axes = ("kv_pages", "page_slot", "kv_heads", "head_dim")
    if cfg.kv_cache_dtype == "int8":
        s_axes = ("kv_pages", "page_slot", "kv_heads")
        return {
            "k": P(jnp.zeros(shape, jnp.int8), axes),
            "v": P(jnp.zeros(shape, jnp.int8), axes),
            "k_scale": P(jnp.zeros(shape[:3], jnp.float32), s_axes),
            "v_scale": P(jnp.zeros(shape[:3], jnp.float32), s_axes),
        }
    return {"k": P(jnp.zeros(shape, dtype), axes),
            "v": P(jnp.zeros(shape, dtype), axes)}


def mla_paged_cache_init(cfg, total_pages, page_size, dtype=jnp.bfloat16):
    """MLA latent page pool: c (P, ps, kv_lora) + k_rope (P, ps, rope)."""
    m = cfg.mla
    pool = {
        "c": P(jnp.zeros((total_pages, page_size, m.kv_lora_rank), dtype),
               ("kv_pages", "page_slot", "kv_lora")),
        "k_rope": P(jnp.zeros((total_pages, page_size, m.qk_rope_dim),
                              dtype),
                    ("kv_pages", "page_slot", "rope_dim")),
    }
    if cfg.kv_cache_dtype == "int8":
        pool["c"] = P(
            jnp.zeros((total_pages, page_size, m.kv_lora_rank), jnp.int8),
            ("kv_pages", "page_slot", "kv_lora"))
        pool["c_scale"] = P(jnp.zeros((total_pages, page_size), jnp.float32),
                            ("kv_pages", "page_slot"))
    return pool


def gqa_decode_paged(params, x, cfg, quant, pool, pt, pos):
    """One paged decode step: x (b,1,d); pt (b,np); pos (b,) int32.

    Identical math to :func:`gqa_decode` — the new token's KV scatters into
    its slot's current page and attention reads the pool through the page
    table (the paged kinds route to the gather oracle off the fused
    backends, so every backend works; only the fused path is gather-free).
    """
    b, _, d = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = qmatmul(params["wq"], x, quant, nh * hd, d).reshape(b, 1, nh, hd)
    k = qmatmul(params["wk"], x, quant, nkv * hd, d).reshape(b, 1, nkv, hd)
    v = qmatmul(params["wv"], x, quant, nkv * hd, d).reshape(b, 1, nkv, hd)
    q = apply_rope(q, pos[:, None], cfg.rope_theta, cfg.rope_scaling)
    k = apply_rope(k, pos[:, None], cfg.rope_theta, cfg.rope_scaling)
    new_pool = {**_paged_store(pool, "k", k, pt, pos=pos),
                **_paged_store(pool, "v", v, pt, pos=pos)}
    new_pool = {
        kk: shard(vv, "kv_pages", "page_slot", "kv_heads", "head_dim"
                  ) if vv.ndim == 4
        else shard(vv, "kv_pages", "page_slot", "kv_heads")
        for kk, vv in new_pool.items()
    }
    scale = 1.0 / math.sqrt(hd)
    out = qattention("paged_decode", q[:, 0], new_pool["k"], new_pool["v"],
                     pt, pos, new_pool.get("k_scale"),
                     new_pool.get("v_scale"), logit_scale=scale)
    out = out[:, None].astype(x.dtype).reshape(b, 1, nh * hd)
    y = qmatmul(params["wo"], out, quant, d, nh * hd)
    return y, new_pool


def mla_decode_paged(params, x, cfg, quant, pool, pt, pos):
    """Paged absorbed-latent MLA decode (see :func:`mla_decode`)."""
    m, d, nh = cfg.mla, cfg.d_model, cfg.num_heads
    b = x.shape[0]
    q_nope, q_rope = _mla_q(params, x, cfg, quant, pos[:, None])
    c_new, k_rope_new = _mla_latents(params, x, cfg, quant, pos[:, None])
    new_pool = {**_paged_store(pool, "c", c_new, pt, pos=pos),
                **_paged_store(pool, "k_rope", k_rope_new, pt, pos=pos)}
    scale = _mla_scale(cfg)
    w_kup = _dequant(params["k_up"], cfg, quant, nh * m.qk_nope_dim,
                     m.kv_lora_rank)
    w_kup = w_kup.reshape(nh, m.qk_nope_dim, m.kv_lora_rank)
    q_lat = f32_einsum("bthn,hnl->bthl", q_nope, w_kup.astype(q_nope.dtype))
    lat = qattention(
        "paged_mla_decode", q_lat[:, 0], q_rope[:, 0], new_pool["c"],
        new_pool["k_rope"], pt, pos, new_pool.get("c_scale"),
        logit_scale=scale)[:, None]
    w_vup = _dequant(params["v_up"], cfg, quant, nh * m.v_head_dim,
                     m.kv_lora_rank)
    w_vup = w_vup.reshape(nh, m.v_head_dim, m.kv_lora_rank)
    out = f32_einsum("bthl,hvl->bthv", lat.astype(w_vup.dtype), w_vup)
    out = out.reshape(b, 1, nh * m.v_head_dim).astype(x.dtype)
    y = qmatmul(params["wo"], out, quant, d, nh * m.v_head_dim)
    return y, new_pool


def gqa_prefill_chunk(params, x, cfg, quant, qpos, pos0, pool, pt):
    """One chunk of paged prefill: x (b, cs, d) at positions ``qpos``
    (b, cs; -1 = dead row), chunk start ``pos0`` (b,) page-aligned.

    The chunk's KV is written into its slot's pages, then the chunk queries
    attend over [gathered prefix window (< pos0) ++ raw in-chunk KV] via
    qattention("chunk_prefill").  Keeping the in-chunk KV *raw* (not read
    back from the pool) makes a single-chunk prefill bit-identical to the
    contiguous prefill even with an int8 pool — the chunk never sees its
    own quantization error, exactly like the contiguous path."""
    b, cs, d = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q, k, v = _gqa_qkv(params, x, cfg, quant, qpos)
    new_pool = {**_paged_store(pool, "k", k, pt, pos0=pos0),
                **_paged_store(pool, "v", v, pt, pos0=pos0)}
    cap = pt.shape[1] * pool["k"].shape[1]
    kw = _paged_window(new_pool, "k", pt, k.dtype)
    vw = _paged_window(new_pool, "v", pt, v.dtype)
    prefix_pos = jnp.arange(cap, dtype=jnp.int32)[None]
    prefix_pos = jnp.where(prefix_pos < pos0[:, None], prefix_pos, -1)
    kcat = jnp.concatenate([kw, k], axis=1)
    vcat = jnp.concatenate([vw, v], axis=1)
    kpos = jnp.concatenate([prefix_pos, qpos], axis=1)
    scale = 1.0 / math.sqrt(hd)
    out = qattention("chunk_prefill", q, kcat, vcat, qpos, kpos,
                     logit_scale=scale)
    out = out.astype(x.dtype).reshape(b, cs, nh * hd)
    return qmatmul(params["wo"], out, quant, d, nh * hd), new_pool


def mla_prefill_chunk(params, x, cfg, quant, qpos, pos0, pool, pt):
    """Chunked paged MLA prefill: latents for the chunk are written to the
    pool; attention runs in the *train* (non-absorbed) form over
    [gathered prefix latents ++ raw chunk latents], up-projected to k/v."""
    m, d, nh = cfg.mla, cfg.d_model, cfg.num_heads
    b, cs, _ = x.shape
    q_nope, q_rope = _mla_q(params, x, cfg, quant, qpos)
    c, k_rope = _mla_latents(params, x, cfg, quant, qpos)
    new_pool = {**_paged_store(pool, "c", c, pt, pos0=pos0),
                **_paged_store(pool, "k_rope", k_rope, pt, pos0=pos0)}
    cap = pt.shape[1] * pool["c"].shape[1]
    cw = _paged_window(new_pool, "c", pt, c.dtype)
    rw = _paged_window(new_pool, "k_rope", pt, k_rope.dtype)
    ccat = jnp.concatenate([cw, c], axis=1)            # (b, cap+cs, L)
    rcat = jnp.concatenate([rw, k_rope], axis=1)       # (b, cap+cs, R)
    W = cap + cs
    k_nope = qmatmul(
        params["k_up"], ccat, quant, nh * m.qk_nope_dim, m.kv_lora_rank
    ).reshape(b, W, nh, m.qk_nope_dim)
    vcat = qmatmul(
        params["v_up"], ccat, quant, nh * m.v_head_dim, m.kv_lora_rank
    ).reshape(b, W, nh, m.v_head_dim)
    kcat = jnp.concatenate(
        [k_nope,
         jnp.broadcast_to(rcat[:, :, None], (b, W, nh, m.qk_rope_dim))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    prefix_pos = jnp.arange(cap, dtype=jnp.int32)[None]
    prefix_pos = jnp.where(prefix_pos < pos0[:, None], prefix_pos, -1)
    kpos = jnp.concatenate([prefix_pos, qpos], axis=1)
    scale = _mla_scale(cfg)
    out = qattention("chunk_prefill", q, kcat, vcat, qpos, kpos,
                     logit_scale=scale)
    out = out.astype(x.dtype).reshape(b, cs, nh * m.v_head_dim)
    return qmatmul(params["wo"], out, quant, d, nh * m.v_head_dim), new_pool
