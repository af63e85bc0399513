"""Weights drawn from the seed for every leaf the paged engine's stacks
have (grouped-query or latent attention, dense or expert MLP), on the CPU
at tiny sizes."""
import hashlib
from pathlib import Path

import numpy as np
import pytest
from chipbench_tiny import latent_model_cfg, tiny_root

from chipbench import harness, weights
from repro.configs import get_config, list_configs, smoke_variant

SEED = 2**31 + 11


def _served_stacks():
    """Registry entries with token input and attention in every layer."""
    out = []
    for name in list_configs():
        cfg = get_config(name)
        if cfg.input_kind == "tokens" and set(cfg.pattern) == {"attn"}:
            out.append(name)
    return out


@pytest.mark.parametrize("name", _served_stacks())
def test_every_served_stack_draws(name):
    cfg = smoke_variant(get_config(name))
    p = weights.flat(weights.draw(cfg, SEED))
    assert p and all(np.isfinite(np.asarray(v, np.float32)).all()
                     for v in p.values())


def test_served_stacks_cover_latent_attention_and_experts():
    kinds = {(get_config(n).attn_kind, get_config(n).moe is not None)
             for n in _served_stacks()}
    assert {("gqa", False), ("gqa", True), ("mla", False)} <= kinds


def test_unknown_leaf_still_raises():
    import jax
    import jax.numpy as jnp

    tree = {"ln1": jax.ShapeDtypeStruct((4,), jnp.float32),
            "mystery": jax.ShapeDtypeStruct((4,), jnp.float32)}
    with pytest.raises(ValueError, match="mystery"):
        weights._draw_tree(weights.seed_key(SEED, 0), tree)


def test_latent_expert_draw():
    """A tiny latent-attention, 8-expert model: every leaf finite; routing
    logits of RMS-normed rows about unit normal; latent norms are gains
    near 1; every expert of a stack gets its own codes and a scale of the
    drawn magnitude."""
    p = weights.flat(weights.draw(latent_model_cfg(), SEED))
    for k, v in p.items():
        assert np.isfinite(np.asarray(v, np.float32)).all(), k
    router = np.asarray(p["layers/blk0/mlp/router"], np.float32)
    assert router.dtype == np.float32 and router.shape[-2:] == (8, 64)
    x = np.random.default_rng(0).standard_normal((4096, 64))
    x /= np.sqrt((x * x).mean(-1, keepdims=True))
    logits = x @ router.reshape(-1, 64).T
    assert 0.85 < logits.std() < 1.15
    for name in ("q_norm", "kv_norm"):
        g = np.asarray(p[f"layers/blk0/mixer/{name}"], np.float32)
        assert abs(g.mean() - 1) < 0.1 and 0.05 < g.std() < 0.2
    codes = np.asarray(p["layers/blk0/mlp/w_gate/q"])
    assert codes.shape[1] == 8
    assert len({codes[:, e].tobytes() for e in range(8)}) == 8
    b = np.asarray(p["layers/blk0/mlp/w_gate/b"], np.float32)
    a = np.asarray(p["layers/blk0/mlp/w_gate/a"], np.float32)
    assert b.shape[:2] == a.shape[:2] == (2, 8)
    scale = np.abs(np.einsum("...nr,...rk->...nk", b, a))
    per_expert = np.median(scale, axis=(-2, -1))
    want = weights.S0 / np.sqrt(a.shape[-1])
    assert np.all((per_expert > 0.7 * want) & (per_expert < 1.3 * want))


def test_qwen_draw_is_unchanged(tmp_path):
    """The Qwen file's draw at a tiny width, as the benchmark drew it
    before latent and expert leaves had rules: codes to the bit, every
    other leaf's sum."""
    cell = harness.load_cell("tiny.batch", root=tiny_root(Path(tmp_path)))
    p = weights.flat(weights.draw(harness.model_config(cell.cfg), 2**31 + 5))
    h = hashlib.sha256()
    for k in sorted(p):
        if p[k].dtype == np.uint8:
            h.update(k.encode())
            h.update(np.asarray(p[k]).tobytes())
    assert h.hexdigest() == ("f91e444b98ea248e62d4d067f32db734"
                             "a2f933afca0141124e567896b56ca8d0")
    sums = {k: float(np.asarray(v, np.float64).sum())
            for k, v in p.items() if v.dtype != np.uint8}
    assert sums == pytest.approx({
        "embed": 7.604365324601531, "final_norm": 65.09062385559082,
        "head": 0.6288602135609835, "layers/blk0/ln1": 125.90579479932785,
        "layers/blk0/ln2": 127.28135043382645,
        "layers/blk0/mixer/wk/a": 3.1409674286842346,
        "layers/blk0/mixer/wk/b": -6.7377587258815765,
        "layers/blk0/mixer/wo/a": -4.69284200668335,
        "layers/blk0/mixer/wo/b": 1.9813979268074036,
        "layers/blk0/mixer/wq/a": 3.86187407374382,
        "layers/blk0/mixer/wq/b": 11.381103098392487,
        "layers/blk0/mixer/wv/a": 5.871276408433914,
        "layers/blk0/mixer/wv/b": 7.760984480381012,
        "layers/blk0/mlp/w_down/a": 2.772812008857727,
        "layers/blk0/mlp/w_down/b": -1.8992699980735779,
        "layers/blk0/mlp/w_gate/a": 3.591609835624695,
        "layers/blk0/mlp/w_gate/b": -11.321394294500351,
        "layers/blk0/mlp/w_up/a": -3.7219755053520203,
        "layers/blk0/mlp/w_up/b": -7.0514466762542725}, rel=1e-6)
