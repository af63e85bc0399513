"""Plain float32 reference of a Qwen3-style dense decoder with LoRDS
linears, written from the published description and imported by nothing
of the program.

Per layer: RMSNorm -> GQA attention with rotate-half RoPE and a causal mask
-> residual -> RMSNorm -> SwiGLU MLP (down(silu(gate(x)) * up(x))) ->
residual; then a final RMSNorm and the output head (the embedding table
where the configuration ties them).  Departure, as in the configuration
file: no per-head q/k RMSNorm.

A LoRDS linear is W = levels[Q] ⊙ (B·A) (the paper's Eq. 1), with Q stored
as slot-major 4-bit planes: byte j of a row holds code j in its low nibble
and code K/2 + j in its high nibble.  The NF4 levels are QLoRA's published
table (``quantization.levels`` of the configuration file).

Every matmul runs at ``precision='highest'``.  ``control=True`` puts each
matmul's operands through float8 e4m3 (one scale per tensor), the
precision one step below the configuration's bfloat16: the check that
decides ``correct`` must fail it.

Weights arrive as a flat dict of arrays under the names of the benchmark's
weight draw (``layers/blk0/mixer/wq/q`` and so on, each with a leading
layer axis).  Work is done layer by layer: one layer's dense weights exist
at a time.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Reference"]

_LINEARS = ("mixer/wq", "mixer/wk", "mixer/wv", "mixer/wo",
            "mlp/w_gate", "mlp/w_up", "mlp/w_down")
_E4M3_MAX = 448.0
_Q_BLOCK = 512          # query rows per attention block
_HI = jax.lax.Precision.HIGHEST


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


@functools.partial(jax.jit, static_argnames=("bits",))
def _dequant(q, b, a, levels, bits):
    if bits != 4:
        raise NotImplementedError("the reference reads 4-bit planes only")
    s = jnp.einsum("nr,rk->nk", b.astype(jnp.float32),
                   a.astype(jnp.float32), precision=_HI)
    return levels[_unpack4(q)] * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # (s, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_math(x, w, ln1, ln2, dims, control):
    """One decoder layer over one sequence x (s, d), positions 0..s-1."""
    nh, nkv, hd, theta, eps = dims
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms(x, ln1, eps)
    q = _rope(_mm(h, w[0], control).reshape(s, nh, hd), pos, theta)
    k = _rope(_mm(h, w[1], control).reshape(s, nkv, hd), pos, theta)
    v = _mm(h, w[2], control).reshape(s, nkv, hd)
    g = nh // nkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    rd = _fp8 if control else (lambda t: t)
    outs = []
    for q0 in range(0, s, _Q_BLOCK):
        qb = q[q0:q0 + _Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", rd(qb), rd(k), precision=_HI)
        sc = sc / math.sqrt(hd)
        causal = pos[None, :] <= (q0 + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", rd(p), rd(v), precision=_HI))
    att = jnp.concatenate(outs, 0).reshape(s, nh * hd)
    x = x + _mm(att, w[3], control)
    h = _rms(x, ln2, eps)
    mid = jax.nn.silu(_mm(h, w[4], control)) * _mm(h, w[5], control)
    return x + _mm(mid, w[6], control)


_layer = jax.jit(_layer_math, static_argnames=("dims", "control"))


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, g, table, eps, control):
    return _mm(_rms(x, g, eps), table.astype(jnp.float32), control)


class Reference:
    """``Reference(cfg, weights).logits(seqs, rows)``: for each token
    sequence (1-D int array), the float32 logits (len(rows), vocab) at the
    given positions, each the prediction of the token after it."""

    def __init__(self, cfg: dict, weights: dict):
        q = cfg["quantization"]
        if q.get("pack_layout") != "planes":
            raise ValueError(f"unknown pack layout {q.get('pack_layout')!r}")
        self.cfg = cfg
        self.w = weights
        self.levels = jnp.asarray(q["levels"], jnp.float32)
        self.bits = {"nf4": 4}[q["codebook"]]
        self.dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"], float(cfg["rope_theta"]),
                     float(cfg["rms_norm_eps"]))

    def _layer_weights(self, layer: int):
        out = []
        for name in _LINEARS:
            base = f"layers/blk0/{name}/"
            out.append(_dequant(self.w[base + "q"][layer],
                                self.w[base + "b"][layer],
                                self.w[base + "a"][layer],
                                self.levels, self.bits))
        return out

    def logits(self, seqs, rows, control: bool = False):
        cfg, w = self.cfg, self.w
        vocab = cfg["vocab_size"]
        emb = w["embed"]
        xs = [jnp.take(emb, jnp.asarray(s, jnp.int32), axis=0)
              .astype(jnp.float32) for s in seqs]
        for layer in range(cfg["num_hidden_layers"]):
            lw = self._layer_weights(layer)
            ln1 = w["layers/blk0/ln1"][layer].astype(jnp.float32)
            ln2 = w["layers/blk0/ln2"][layer].astype(jnp.float32)
            xs = [_layer(x, lw, ln1, ln2, self.dims, control) for x in xs]
            del lw
        table = emb if cfg["tie_word_embeddings"] else w["head"]
        table = table[:vocab]
        g = w["final_norm"].astype(jnp.float32)
        out = []
        for x, r in zip(xs, rows):
            lg = _head(x[jnp.asarray(r, jnp.int32)], g, table,
                       float(cfg["rms_norm_eps"]), control)
            out.append(np.asarray(lg, np.float32))
        return out

