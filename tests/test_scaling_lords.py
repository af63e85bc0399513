"""LoRDS scaling decomposition: parity ranks (paper Table 7), SVD init
exactness, PTQ refinement (Alg. 1), STE gradients (Eq. 4/5), PEFT partition.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    QuantSpec,
    dequantize_weight,
    fake_quant_ste,
    init_quantized_linear,
    ptq_refine,
)
from repro.core import lut, metrics, peft, quantize, scaling


# paper Appendix A Table 7 — exact rank parity values
TABLE7 = [
    # (n, m, block, rank)
    (4096, 4096, 128, 16), (1024, 4096, 128, 6), (14336, 4096, 128, 24),
    (4096, 14336, 128, 24), (4096, 4096, 256, 8), (1024, 4096, 256, 3),
    (12288, 4096, 128, 24), (4096, 2560, 128, 12), (1024, 2560, 128, 5),
    (9728, 2560, 128, 15), (1024, 2560, 256, 2), (9728, 2560, 256, 7),
]


@pytest.mark.parametrize("n,m,bs,r", TABLE7)
def test_parity_rank_matches_paper_table7(n, m, bs, r):
    assert scaling.parity_rank(n, m, bs) == r


def test_svd_init_exact_when_rank_sufficient(key):
    """r >= rank(S_blockwise) ==> BA reproduces S exactly (Eq. 3)."""
    w = jax.random.normal(key, (64, 256)) * 0.02
    s_blk = scaling.blockwise_scales(w, 64)          # rank <= 4
    s_dense = scaling.expand_block_scales(s_blk, 64)
    b, a = scaling.svd_init(s_dense, 4)
    np.testing.assert_allclose(np.asarray(b @ a), np.asarray(s_dense),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("rank", [2, 4])
@pytest.mark.parametrize("smooth", [False, True])
def test_svd_init_blocks_matches_dense_svd(key, rank, smooth):
    """The factored SVD of the (n, m/B) block scales gives the same rank-r
    truncation of S as the dense SVD of the expanded (n, m) S."""
    w = jax.random.normal(key, (48, 256)) * 0.02
    c = (jnp.exp(jax.random.normal(jax.random.PRNGKey(3), (256,)) * 0.5)
         if smooth else None)
    s_blk = scaling.blockwise_scales(w if c is None else w * c, 64)
    s_dense = scaling.expand_block_scales(s_blk, 64)
    if c is not None:
        s_dense = s_dense / c[None, :]
    b0, a0 = scaling.svd_init(s_dense, rank)
    b, a = scaling.svd_init_blocks(s_blk, 64, rank, c)
    assert b.shape == b0.shape and a.shape == a0.shape
    np.testing.assert_allclose(np.asarray(b @ a), np.asarray(b0 @ a0),
                               rtol=1e-4, atol=1e-6)


def test_ptq_refinement_beats_blockwise(key):
    """The paper's central PTQ claim at parity budget: refined continuous
    low-rank scaling reconstructs better than rigid block-wise scaling."""
    w = jax.random.normal(key, (128, 512)) * 0.02
    qb, sb = quantize.quantize_blockwise(w, 128, "nf4")
    w_block = quantize.dequantize_blockwise(qb, sb, 128, "nf4")
    err_block = float(metrics.frobenius_error(w, w_block))

    res = ptq_refine(w, steps=150, lr=0.05, block_size=128)
    s = scaling.scale_matrix(res.b, res.a)
    codes = quantize.unpack_codes(res.q_packed, "nf4")
    w_lords = quantize.dequantize_codes(codes, s, "nf4")
    err_lords = float(metrics.frobenius_error(w, w_lords))
    assert err_lords < err_block
    # loss history is (noisily) decreasing overall
    lh = np.asarray(res.loss_history)
    assert lh[-10:].mean() < lh[:10].mean()


def test_ste_gradients_match_paper_equations(key):
    """∇_W = g (Eq. 4); ∇_S = g ⊙ (Q − W⊘S) (Eq. 5)."""
    w = jax.random.normal(key, (4, 8)) * 0.1
    s = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (4, 8))) + 0.05
    g = jax.random.normal(jax.random.PRNGKey(8), (4, 8))

    f = lambda w_, s_: jnp.sum(fake_quant_ste("nf4", w_, s_) * g)
    gw, gs = jax.grad(f, argnums=(0, 1))(w, s)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(g), rtol=1e-6)

    codes = quantize.quantize_codes(w, s, "nf4")
    qv = jnp.take(lut.codebook("nf4"), codes.astype(jnp.int32))
    expect = np.asarray(g * (qv - w / s))
    np.testing.assert_allclose(np.asarray(gs), expect, rtol=1e-5, atol=1e-6)


def test_peft_partition_modes(key):
    w = jax.random.normal(key, (64, 128)) * 0.02
    spec = QuantSpec(method="lords", block_size=64, rank=2, mode="peft")
    params = init_quantized_linear(key, 64, 128, spec, w=w)
    t, f = peft.partition(params, spec)
    assert t["q"] is None and f["q"] is not None
    assert t["b"] is not None and t["a"] is not None
    back = peft.combine(t, f)
    for k in params:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(params[k]))
    # qat mode trains w too
    spec_q = spec.with_(mode="qat")
    params_q = init_quantized_linear(key, 64, 128, spec_q, w=w)
    t2, f2 = peft.partition(params_q, spec_q)
    assert t2["w"] is not None and t2["q"] is None if "q" in params_q else True


def test_peft_multiplicative_update_is_high_rank(key):
    """Fig. 3 claim: ΔW = Q ⊙ (B'A' − BA) has rank >> r."""
    n, m, r = 96, 192, 2
    w = jax.random.normal(key, (n, m)) * 0.02
    spec = QuantSpec(method="lords", block_size=64, rank=r, mode="peft")
    params = init_quantized_linear(key, n, m, spec, w=w)
    w0 = dequantize_weight(params, spec, n, m).astype(jnp.float32)
    # simulate a PEFT update on B, A
    kb, ka = jax.random.split(jax.random.PRNGKey(5))
    params2 = dict(params)
    params2["b"] = params["b"] + 0.1 * jax.random.normal(kb, params["b"].shape)
    params2["a"] = params["a"] + 0.1 * jax.random.normal(ka, params["a"].shape)
    w1 = dequantize_weight(params2, spec, n, m).astype(jnp.float32)
    delta = w1 - w0
    eff = int(metrics.effective_rank(delta, rel_tol=1e-2))
    assert eff > 4 * r, f"effective rank {eff} should far exceed r={r}"


def test_lords_dagger_extra_rank(key):
    """LoRDS† (Appendix B): r = parity + r_q."""
    spec = QuantSpec(method="lords", block_size=128, extra_rank=16)
    assert spec.lords_rank(4096, 4096) == 16 + 16


def test_channel_scale_folds_into_svd_init(key):
    """Init with channel_scale c must equal block scales of the *smoothed*
    weight divided back by c — so quantizing W against it is exactly
    quantizing W ⊙ c against its own block scales (AWQ-style smoothing at
    zero runtime cost; diagonal scaling preserves the S rank)."""
    w = jax.random.normal(key, (64, 256)) * 0.02
    c = jnp.exp(jax.random.normal(jax.random.PRNGKey(5), (256,)) * 0.5)
    b, a = scaling.lords_init_from_weight(w, 64, rank=4, channel_scale=c)
    s_fold = scaling.expand_block_scales(
        scaling.blockwise_scales(w * c[None, :], 64), 64) / c[None, :]
    np.testing.assert_allclose(np.asarray(b @ a), np.asarray(s_fold),
                               rtol=1e-4, atol=1e-6)


def test_ptq_refine_col_weight_prioritizes_heavy_columns(key):
    """Activation-weighted refinement must reduce the weighted recon error
    at least as well as unweighted refinement does."""
    w = jax.random.normal(key, (64, 128)) * 0.02
    colw = jnp.ones((128,)).at[:8].set(100.0)  # heavy leading channels

    def werr(res):
        s = scaling.scale_matrix(res.b, res.a)
        codes = quantize.unpack_codes(res.q_packed, "nf4")
        w_hat = quantize.dequantize_codes(codes, s, "nf4")
        return float(jnp.mean(((w - w_hat) ** 2) * colw[None, :]))

    plain = ptq_refine(w, "nf4", 32, rank=3, steps=40)
    weighted = ptq_refine(w, "nf4", 32, rank=3, steps=40, col_weight=colw)
    assert werr(weighted) <= werr(plain) * 1.0001
