"""The dropless grouped expert layer, DeepSeek-style routing and YaRN rope
on the CPU: no assignment dropped however the rows route, the row bound,
the grouped kernel (interpret mode) against the per-tile ``qmatmul`` path,
the router's gates, and the rope tables against their closed forms."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import YarnCfg, get_config, smoke_variant
from repro.core import QuantSpec
from repro.kernels.dispatch import qmatmul_experts
from repro.models import moe as moe_mod
from repro.models import split_tree
from repro.models.common import rope_attn_scale, rope_freqs

F32 = QuantSpec(method="none", mode="frozen", compute_dtype=jnp.float32)


def _layer(key, e=4, k=2, d_ff=32, **moe_kw):
    cfg = smoke_variant(get_config("phi3.5-moe-42b-a6.6b"))
    cfg = cfg.with_(moe=cfg.moe.__class__(num_experts=e, top_k=k, d_ff=d_ff,
                                          **moe_kw))
    params, _ = split_tree(moe_mod.moe_init(key, cfg, F32))
    return cfg, params


def _per_token(params, cfg, x, live):
    """Each live token through its top-k experts, one at a time."""
    mo = cfg.moe
    xf = np.asarray(x.reshape(-1, cfg.d_model), np.float32)
    gates, idx, _ = moe_mod._route(params, jnp.asarray(xf), mo)
    wg, wu, wd = (np.asarray(params[n]["w"]) for n in
                  ("w_gate", "w_up", "w_down"))
    y = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        if not live[t]:
            continue
        for j in range(mo.top_k):
            e = int(idx[t, j])
            h = np.asarray(jax.nn.silu(wg[e] @ xf[t])) * (wu[e] @ xf[t])
            y[t] += float(gates[t, j]) * (wd[e] @ h)
    return y


@pytest.mark.parametrize("skew", ["one_expert", "random"])
def test_grouped_layer_drops_nothing_and_keeps_the_row_bound(key, skew):
    cfg, params = _layer(key, e=4, k=2)
    b, s, d = 3, 16, cfg.d_model
    x = jax.random.normal(key, (b, s, d), jnp.float32) * 0.3
    if skew == "one_expert":
        # every row's logits favour experts 0 and 1 by a wide margin
        x = jnp.abs(x)
        r = np.zeros((4, d), np.float32)
        r[0], r[1] = 10.0, 5.0
        params = dict(params, router=jnp.asarray(r))
    live = np.ones(b * s, bool)
    live[5:9] = False
    y, stats = moe_mod.moe_apply_grouped(params, x, cfg, F32,
                                         jnp.asarray(live.reshape(b, s)))
    want = _per_token(params, cfg, x, live)
    np.testing.assert_allclose(np.asarray(y.reshape(-1, d)), want,
                               rtol=2e-4, atol=2e-4)
    hits, assigned, rows = (int(v) for v in stats)
    n_live, k, e = int(live.sum()), cfg.moe.top_k, cfg.moe.num_experts
    tile = moe_mod.expert_row_tile(b * s, k, e)
    assert assigned == n_live * k
    assert rows <= n_live * k + e * (tile - 1) and rows % tile == 0
    if skew == "one_expert":
        assert hits == 2 and rows == 2 * (-(-n_live // tile) * tile)


def test_dead_rows_route_nowhere(key):
    cfg, params = _layer(key)
    x = jax.random.normal(key, (2, 8, cfg.d_model), jnp.float32)
    y, stats = moe_mod.moe_apply_grouped(params, x, cfg, F32,
                                         jnp.zeros((2, 8), bool))
    assert not np.asarray(y).any() and not np.asarray(stats).any()


def test_group_rows_sorts_by_expert_and_pads_each_group():
    idx = jnp.asarray([[0, 1], [1, 2], [0, 3], [3, 1], [1, 0]])
    live = jnp.asarray([True, True, False, True, True])
    pos, (te, tr, tl), counts = moe_mod.group_rows(idx, live, 4, 8)
    assert list(np.asarray(counts)) == [2, 4, 1, 1]
    pos = np.asarray(pos)
    assert list(pos[4:6]) == [40, 40]          # the dead row's assignments
    flat = np.asarray(idx).reshape(-1)
    for a, p in enumerate(pos):
        if p < 40:
            assert int(te[p // 8]) == flat[a]
    assert list(np.asarray(tl)) == [1, 1, 1, 1, 0]
    assert int(tr[4]) == 3 and int(te[4]) == int(te[3])


@pytest.mark.parametrize("n,k", [(96, 192), (64, 256)])
def test_grouped_kernel_matches_per_tile_qmatmul(n, k):
    """Interpret mode of the Pallas kernel against each tile's expert
    through ``qmatmul`` (ref): K = 192 has planes of 96 codes, padded to
    the lane tile (the K = 1408 case at a small size)."""
    quant = QuantSpec(method="lords", codebook="nf4", block_size=32,
                      mode="peft", rank=2)
    e = 4
    p, _ = split_tree(moe_mod._qlinear_stack_init(jax.random.PRNGKey(1), e,
                                                  n, k, quant))
    idx = jnp.asarray([[0, 1], [1, 2], [0, 3], [3, 1], [1, 0], [2, 2]])
    live = jnp.asarray([True, True, False, True, True, True])
    _, tiles, _ = moe_mod.group_rows(idx, live, e, 8)
    rows = tiles[0].shape[0] * 8
    x = jax.random.normal(jax.random.PRNGKey(2), (rows, k)).astype(
        jnp.bfloat16)
    args = (p, x, tiles + (8,), quant, n, k)
    want = np.asarray(qmatmul_experts(*args, backend="ref"), np.float32)
    got = np.asarray(qmatmul_experts(*args, backend="interpret"), np.float32)
    keep = np.repeat(np.asarray(tiles[2]), 8).astype(bool)
    np.testing.assert_allclose(got[keep], want[keep], rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("renorm", [None, True, False])
def test_gates_are_the_softmax_renormalised_only_where_asked(key, renorm):
    cfg, params = _layer(key, e=8, k=3, norm_topk_prob=renorm,
                         routed_scaling_factor=None if renorm is None
                         else 1.5)
    x = jax.random.normal(key, (10, cfg.d_model), jnp.float32)
    gates, idx, _ = moe_mod._route(params, x, cfg.moe)
    probs = jax.nn.softmax(x @ params["router"].T, -1)
    top = np.sort(np.asarray(probs), -1)[:, ::-1][:, :3]
    if renorm is False:
        want = top * 1.5
    else:
        want = top / top.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(gates), want, rtol=1e-5)


def test_yarn_tables_equal_the_closed_forms():
    """DeepSeek-V2-Lite's rope: 64 dims, θ 1e4, factor 40, original
    context 4096, β 32 and 1, mscale = mscale_all_dim = 0.707."""
    y = get_config("deepseek-v2-lite").rope_scaling
    dim, theta = 64, 10000.0
    inv = 1.0 / theta ** (np.arange(0, dim, 2) / dim)

    def dim_of(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (
            2 * math.log(theta))

    low, high = math.floor(dim_of(32)), math.ceil(dim_of(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = inv * (1 - ramp) + inv / 40 * ramp
    np.testing.assert_allclose(np.asarray(rope_freqs(dim, theta, y)), want,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(rope_freqs(dim, theta)), inv,
                               rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.2608, abs=1e-4)
    assert rope_attn_scale(192, y) == pytest.approx(mscale**2 / 192**0.5)
    assert rope_attn_scale(192) == 1 / 192**0.5
    assert rope_attn_scale(192, YarnCfg(factor=40.0,
                                        original_max_position_embeddings=4096
                                        )) == 1 / 192**0.5
