"""Required per-arch smoke tests: reduced config of the same family, one
forward/train step on CPU, asserting output shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, list_configs, smoke_variant
from repro.core import peft
from repro.models import forward_train, model_init, split_tree

ALL_ARCHS = [
    "minicpm3-4b", "minitron-4b", "llama3-405b", "granite-20b",
    "phi3.5-moe-42b-a6.6b", "kimi-k2-1t-a32b", "internvl2-1b", "xlstm-1.3b",
    "musicgen-medium", "jamba-1.5-large-398b", "deepseek-v2-lite",
    # the paper's own models
    "llama3-8b", "qwen3-8b", "qwen3-4b",
]


def test_registry_covers_assignment():
    have = set(list_configs())
    for arch in ALL_ARCHS:
        assert arch in have, f"missing assigned arch {arch}"
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}


def _batch(cfg, key, b=2, s=32):
    labels = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    if cfg.input_kind == "tokens":
        return {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
                "labels": labels}
    return {"embeds": jax.random.normal(key, (b, s, cfg.d_model),
                                        jnp.float32),
            "labels": labels}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_and_train_step(arch, key):
    cfg = smoke_variant(get_config(arch))
    params, axes = split_tree(model_init(key, cfg))
    batch = _batch(cfg, key)

    loss, metrics = forward_train(params, cfg, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: loss {float(loss)}"

    # one PEFT train step: grads flow to B/A only and update them
    trainable, frozen = peft.partition(params, cfg.quant)

    def loss_fn(t):
        return forward_train(peft.combine(t, frozen), cfg, batch)[0]

    grads = jax.grad(loss_fn)(trainable)
    gnorm = sum(float(jnp.sum(jnp.abs(g)))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, f"{arch}: bad grads"
    # exact-shape parity between grads and trainable
    for g, t in zip(jax.tree.leaves(grads), jax.tree.leaves(trainable)):
        assert g.shape == t.shape


@pytest.mark.parametrize("arch", ["llama3-8b", "kimi-k2-1t-a32b"])
def test_smoke_qat_mode(arch, key):
    cfg = smoke_variant(get_config(arch))
    cfg = cfg.with_(quant=cfg.quant.with_(mode="qat"))
    params, _ = split_tree(model_init(key, cfg))
    batch = _batch(cfg, key)
    trainable, frozen = peft.partition(params, cfg.quant)

    def loss_fn(t):
        return forward_train(peft.combine(t, frozen), cfg, batch)[0]

    loss, grads = jax.value_and_grad(loss_fn)(trainable)
    assert np.isfinite(float(loss))
    # master weights receive STE gradients
    gw = grads["layers"]["blk0"]["mixer"]["wq"]["w"]
    assert float(jnp.sum(jnp.abs(gw))) > 0
