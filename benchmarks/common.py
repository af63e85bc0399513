"""Shared benchmark infrastructure.

The paper's quality tables use 4–8B checkpoints + WikiText/PTB; this
container is CPU-only and offline, so every benchmark runs the same
*algorithms* at laptop scale and checks the paper's *orderings*:

  * realistic weight matrices: gaussian base + per-row/column scale structure
    + persistent outlier channels (what block-wise scaling actually fights),
  * tiny LMs trained on the deterministic synthetic stream for PPL-direction
    claims (eval loss == log-PPL on the held-out stream).
"""
from __future__ import annotations

import os
import time

import jax

if jax.default_backend() == "cpu":
    # XLA:CPU cannot execute bf16 x bf16 -> f32 dots (models/common.py)
    os.environ.setdefault("REPRO_CPU_EXEC", "1")
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig, ShapeCfg, get_config, smoke_variant
from repro.core import QuantSpec, peft
from repro.data import SyntheticLM
from repro.models import forward_train, model_init, split_tree

__all__ = ["realistic_weight", "tiny_lm", "train_tiny", "eval_loss",
           "quantize_model_weights", "timer", "MODULE_SHAPES"]

# llama3-8b module shapes scaled 1/4 (aspect ratios preserved) — Table 8 rows
MODULE_SHAPES = {
    "Q": (1024, 1024), "K": (256, 1024), "V": (256, 1024), "O": (1024, 1024),
    "Gate": (3584, 1024), "Up": (3584, 1024), "Down": (1024, 3584),
}


def realistic_weight(key, n, m, outlier_frac=0.01, outlier_gain=8.0,
                     row_scale_spread=1.0):
    """LLM-like weight: gaussian + log-normal row scales + outlier columns."""
    k1, k2, k3 = jax.random.split(key, 3)
    base = jax.random.normal(k1, (n, m)) * 0.02
    row_scale = jnp.exp(row_scale_spread
                        * jax.random.normal(k2, (n, 1)) * 0.4)
    w = base * row_scale
    n_out = max(1, int(m * outlier_frac))
    idx = jax.random.choice(k3, m, (n_out,), replace=False)
    w = w.at[:, idx].multiply(outlier_gain)
    return w


def tiny_lm(quant: QuantSpec, layers=2, d=128, heads=4, d_ff=256,
            vocab=512) -> ModelConfig:
    return get_config("llama3-8b").with_(
        name="tiny-lm", num_layers=layers, d_model=d, num_heads=heads,
        num_kv_heads=heads, d_ff=d_ff, vocab_size=vocab,
        vocab_pad_multiple=64, head_dim=d // heads, quant=quant, remat=False)


def _batches(cfg, shape, seed, n):
    src = SyntheticLM(cfg.vocab_size, shape.seq_len, shape.global_batch,
                      seed=seed)
    return [src.batch_at(i) for i in range(n)]


def train_tiny(cfg, steps=200, lr=2e-3, seed=0, seq=64, batch=8,
               params=None, schedule=None):
    """Train (or fine-tune) a tiny LM; returns (params, loss_history)."""
    from repro.optim import adamw_init, adamw_update

    shape = ShapeCfg("bench", seq, batch, "train")
    key = jax.random.PRNGKey(seed)
    if params is None:
        params, _ = split_tree(model_init(key, cfg))
    trainable, frozen = peft.partition(params, cfg.quant)
    opt = adamw_init(trainable)

    @jax.jit
    def step(trainable, opt, batch):
        def loss_fn(t):
            return forward_train(peft.combine(t, frozen), cfg, batch)[0]

        loss, grads = jax.value_and_grad(loss_fn)(trainable)
        new_t, new_opt, _ = adamw_update(trainable, grads, opt, lr)
        return new_t, new_opt, loss

    losses = []
    src = SyntheticLM(cfg.vocab_size, seq, batch, seed=seed)
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in src.batch_at(i).items()}
        trainable, opt, loss = step(trainable, opt, b)
        losses.append(float(loss))
    return peft.combine(trainable, frozen), losses


def eval_loss(params, cfg, seed=10_000, n_batches=8, seq=64, batch=8):
    shape = ShapeCfg("eval", seq, batch, "train")
    src = SyntheticLM(cfg.vocab_size, seq, batch, seed=seed)

    @jax.jit
    def one(params, b):
        return forward_train(params, cfg, b)[0]

    tot = 0.0
    for i in range(n_batches):
        b = {k: jnp.asarray(v) for k, v in src.batch_at(i).items()}
        tot += float(one(params, b))
    return tot / n_batches


def quantize_model_weights(params_fp, cfg_fp, quant: QuantSpec, refine=0,
                           lr=0.05):
    """Re-quantize a trained fp tiny-LM's linears under ``quant``.

    Walks the param tree, replacing each {'w': ...} linear with the target
    format (blockwise / lords / adapters), optionally running Alg.-1
    refinement per matrix.  Returns params for cfg_fp.with_(quant=quant).
    """
    from repro.core import init_quantized_linear, ptq_refine
    from repro.core.quantize import pack_codes, quantize_codes
    from repro.core.scaling import scale_matrix

    key = jax.random.PRNGKey(0)

    def convert_one(w):
        n, m = w.shape
        if quant.method == "lords" and refine:
            res = ptq_refine(w, quant.codebook, quant.block_size,
                             rank=quant.rank, extra_rank=quant.extra_rank,
                             steps=refine, lr=lr)
            return {"q": res.q_packed, "b": res.b, "a": res.a}
        return init_quantized_linear(key, n, m, quant, w=w)

    def walk(node):
        if isinstance(node, dict) and set(node) >= {"w"} and hasattr(
                node["w"], "ndim") and len(node) <= 2:
            w = node["w"].astype(jnp.float32)
            if w.ndim == 2:
                return convert_one(w)
            if w.ndim == 3:  # stacked scan periods: vmap the conversion
                return jax.vmap(convert_one)(w)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(params_fp)


class timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.dt = time.perf_counter() - self.t0
