"""Plain float32 reference of DeepSeek-V2(-Lite) with LoRDS linears, written
from the published description and imported by nothing of the program.

Per layer: RMSNorm -> multi-head latent attention -> residual -> RMSNorm
-> MLP -> residual; then a final RMSNorm and the output head.

Latent attention without a q LoRA: q = Wq·h per head, split into a part
without position (``qk_nope_head_dim``) and a rope part
(``qk_rope_head_dim``); [c, k_pe] = W_kv_a·h, c RMS-normed
(``kv_lora_rank``); per head k = [W_k·c, rope(k_pe)] (k_pe shared by every
head) and v = W_v·c; full causal softmax attention with scale
``mscale² / sqrt(nope + rope)``; then Wo.  Rope is YaRN (``rope_scaling``):
inverse frequencies blended between θ-frequencies and θ-frequencies ÷
factor by a linear ramp between the dims that turn ``beta_fast`` and
``beta_slow`` times over the original context, cos and sin scaled by
``mscale(mscale) / mscale(mscale_all_dim)``, ``mscale(m) = 0.1·m·ln(factor)
+ 1``; the softmax scale takes ``mscale(mscale_all_dim)²``.

The MLP is a dense SwiGLU (``intermediate_size``) in the first
``first_k_dense_replace`` layers.  Every later layer is a mixture of
experts computed without capacity (no token is dropped): router logits
W_r·h in float32, softmax over ``n_routed_experts``, the
``num_experts_per_tok`` largest (greedy), gates renormalised only where
``norm_topk_prob`` is true and otherwise times ``routed_scaling_factor``;
the output is the gated sum of the chosen experts' SwiGLUs of
``moe_intermediate_size`` plus the shared experts, one SwiGLU of
``n_shared_experts × moe_intermediate_size`` every token goes through.
The routing is this reference's own, on its own float32 logits.

Departures, as in the configuration file: rope pairs dim i with dim
i + rope/2 (DeepSeek pairs adjacent dims), and kv_b is held as two
linears, k_up and v_up; with weights drawn from the seed both are fixed
permutations of the same model.

A LoRDS linear is W = levels[Q] ⊙ (B·A), Q stored as slot-major 4-bit
planes (byte j of a row: code j in the low nibble, code K/2 + j in the
high one); expert-stacked linears carry a leading expert axis.  Every
matmul runs at ``precision='highest'``; ``control=True`` puts each
matmul's operands (router included) through float8 e4m3 (one scale per
tensor), the precision one step below the configuration's bfloat16: the
check that decides ``correct`` must fail it.

Weights arrive as a flat dict under the names of the benchmark's weight
draw (``lead/blk0/...`` for the leading dense layers, ``layers/blk0/...``
for the expert layers, each with a leading layer axis).  Work is done layer
by layer, and the experts of a layer one at a time: at most one expert's
dense weights exist at once.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Reference"]

_E4M3_MAX = 448.0
_Q_BLOCK = 512          # query rows per attention block
_HI = jax.lax.Precision.HIGHEST
_ATTN = ("wq", "kv_down", "k_up", "v_up", "wo")
_SWIGLU = ("w_gate", "w_up", "w_down")


def _fp8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = _E4M3_MAX / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(a, b, control):
    """a (..., k) @ b (n, k)^T in float32 (or fp8 operands)."""
    if control:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum("...k,nk->...n", a, b, precision=_HI)


def _unpack4(q):
    q = q.astype(jnp.int32)
    return jnp.concatenate([q & 15, q >> 4], axis=-1)


def _dequant(q, b, a, levels):
    s = jnp.einsum("nr,rk->nk", b.astype(jnp.float32),
                   a.astype(jnp.float32), precision=_HI)
    return levels[_unpack4(q)] * s


_dequant_jit = jax.jit(_dequant)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_tables(cfg, seq_len):
    """(cos, sin) (seq_len, rope/2) of YaRN rope, and the softmax scale."""
    dim = cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_theta"])
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + dim)
    cs = 1.0
    rs = cfg.get("rope_scaling")
    if rs is not None:
        if rs["type"] != "yarn":
            raise NotImplementedError(f"rope scaling {rs['type']!r}")
        f, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

        def dim_of(rot):
            return (dim * math.log(orig / (rot * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(dim_of(rs["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        inv = inv / f * ramp + inv * (1.0 - ramp)
        cs = _mscale(f, rs["mscale"]) / _mscale(f, rs["mscale_all_dim"])
        if rs.get("mscale_all_dim"):
            scale *= _mscale(f, rs["mscale_all_dim"]) ** 2
    ang = np.arange(seq_len, dtype=np.float64)[:, None] * inv[None]
    return (jnp.asarray(np.cos(ang) * cs, jnp.float32),
            jnp.asarray(np.sin(ang) * cs, jnp.float32), scale)


def _rope(x, cos, sin):
    """x (s, ..., rope): dim i paired with dim i + rope/2."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(x, w, ln1, kv_norm, cos, sin, dims, control):
    """x + attention(RMSNorm(x)) over one sequence x (s, d), positions
    0..s-1."""
    nh, nope, rope, v_dim, lat, scale, eps = dims
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms(x, ln1, eps)
    q = _mm(h, w[0], control).reshape(s, nh, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], cos, sin)
    ckv = _mm(h, w[1], control)
    c = _rms(ckv[:, :lat], kv_norm, eps)
    k_pe = _rope(ckv[:, lat:], cos, sin)                     # (s, rope)
    k_nope = _mm(c, w[2], control).reshape(s, nh, nope)
    v = _mm(c, w[3], control).reshape(s, nh, v_dim)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], (s, nh, rope))], -1)
    qf = jnp.concatenate([q_nope, q_pe], -1)
    rd = _fp8 if control else (lambda t: t)
    outs = []
    for q0 in range(0, s, _Q_BLOCK):
        qb = qf[q0:q0 + _Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", rd(qb), rd(k), precision=_HI) * scale
        causal = pos[None, :] <= (q0 + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", rd(p), rd(v), precision=_HI))
    att = jnp.concatenate(outs, 0).reshape(s, nh * v_dim)
    return x + _mm(att, w[4], control)


_attention_jit = jax.jit(_attention, static_argnames=("dims", "control"))


def _swiglu(h, wg, wu, wd, control):
    mid = jax.nn.silu(_mm(h, wg, control)) * _mm(h, wu, control)
    return _mm(mid, wd, control)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _dense_mlp(x, ln2, wg, wu, wd, eps, control):
    return x + _swiglu(_rms(x, ln2, eps), wg, wu, wd, control)


@functools.partial(jax.jit, static_argnames=("route", "eps", "control"))
def _moe_mlp(x, ln2, router, experts, shared, levels, route, eps, control):
    """x + MoE(RMSNorm(x)) over rows x (n, d): every expert in turn, its
    weights dequantized inside the loop, its output gated per row (zero
    for rows that did not choose it)."""
    k, renorm, factor = route
    h = _rms(x, ln2, eps)
    probs = jax.nn.softmax(_mm(h, router, control), axis=-1)   # (n, E)
    gates, idx = jax.lax.top_k(probs, k)
    if renorm:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    else:
        gates = gates * factor
    rows = jnp.arange(h.shape[0])[:, None]
    gate = jnp.zeros_like(probs).at[rows, idx].set(gates)      # (n, E)

    def one(acc, inp):
        lin, g = inp
        w = [_dequant(*lin[i], levels) for i in range(3)]
        return acc + g[:, None] * _swiglu(h, *w, control), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (experts, gate.T))
    y = y + _swiglu(h, *shared, control)
    return x + y


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, g, table, eps, control):
    return _mm(_rms(x, g, eps), table.astype(jnp.float32), control)


class Reference:
    """``Reference(cfg, weights).logits(seqs, rows)``: for each token
    sequence (1-D int array), the float32 logits (len(rows), vocab) at the
    given positions, each the prediction of the token after it."""

    def __init__(self, cfg: dict, weights: dict):
        q = cfg["quantization"]
        if q.get("pack_layout") != "planes" or q["codebook"] != "nf4":
            raise ValueError("the reference reads nf4 codes in planes")
        if cfg.get("q_lora_rank") is not None:
            raise NotImplementedError("q through a LoRA")
        if cfg.get("scoring_func", "softmax") != "softmax" or cfg.get(
                "topk_method", "greedy") != "greedy" or cfg.get(
                "n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
            raise NotImplementedError("softmax greedy top-k routing only")
        if cfg.get("moe_layer_freq", 1) != 1:
            raise NotImplementedError("experts in every later layer only")
        self.cfg = cfg
        self.w = weights
        self.levels = jnp.asarray(q["levels"], jnp.float32)
        self.eps = float(cfg["rms_norm_eps"])
        self.lead = cfg.get("first_k_dense_replace") or 0
        self.route = (cfg["num_experts_per_tok"],
                      bool(cfg.get("norm_topk_prob", False)),
                      float(cfg.get("routed_scaling_factor", 1.0)))

    def _linear(self, name, layer):
        return _dequant_jit(self.w[name + "/q"][layer],
                            self.w[name + "/b"][layer],
                            self.w[name + "/a"][layer], self.levels)

    def _layer_name(self, i):
        """(prefix, index in its stack) of layer i."""
        if i < self.lead:
            return "lead/blk0/", i
        return "layers/blk0/", i - self.lead

    def logits(self, seqs, rows, control: bool = False):
        cfg, w = self.cfg, self.w
        vocab = cfg["vocab_size"]
        emb = w["embed"]
        xs = [jnp.take(emb, jnp.asarray(s, jnp.int32), axis=0)
              .astype(jnp.float32) for s in seqs]
        cos, sin, scale = _rope_tables(cfg, max(len(s) for s in seqs))
        dims = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                cfg["kv_lora_rank"], scale, self.eps)
        lens = [x.shape[0] for x in xs]
        for i in range(cfg["num_hidden_layers"]):
            pre, j = self._layer_name(i)
            aw = [self._linear(pre + "mixer/" + n, j) for n in _ATTN]
            ln1 = w[pre + "ln1"][j].astype(jnp.float32)
            kvn = w[pre + "mixer/kv_norm"][j].astype(jnp.float32)
            xs = [_attention_jit(x, aw, ln1, kvn, cos[:x.shape[0]],
                                 sin[:x.shape[0]], dims, control)
                  for x in xs]
            del aw
            ln2 = w[pre + "ln2"][j].astype(jnp.float32)
            x = jnp.concatenate(xs, 0)
            if i < self.lead:
                mw = [self._linear(pre + "mlp/" + n, j) for n in _SWIGLU]
                x = _dense_mlp(x, ln2, *mw, self.eps, control)
            else:
                experts = tuple(
                    tuple(w[f"{pre}mlp/{n}/{leaf}"][j] for leaf in "qba")
                    for n in _SWIGLU)
                shared = [self._linear(pre + "mlp/shared/" + n, j)
                          for n in _SWIGLU]
                x = _moe_mlp(x, ln2, w[pre + "mlp/router"][j]
                             .astype(jnp.float32), experts, shared,
                             self.levels, self.route, self.eps, control)
                del shared
            xs = list(jnp.split(x, np.cumsum(lens)[:-1]))
        table = emb if cfg["tie_word_embeddings"] else w["head"]
        table = table[:vocab]
        g = w["final_norm"].astype(jnp.float32)
        out = []
        for x, r in zip(xs, rows):
            lg = _head(x[jnp.asarray(r, jnp.int32)], g, table, self.eps,
                       control)
            out.append(np.asarray(lg, np.float32))
        return out
