"""Pallas kernels vs pure-jnp oracles: shape/dtype/codebook sweeps in
interpret mode (the kernel body executes on CPU), exactly as required.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # optional-dep shim

from repro.core import quantize, scaling
from repro.kernels import ops, ref

SHAPES = [  # (M, N, K, blocks)
    (64, 128, 256, dict(bm=32, bn=64, bk=128)),
    (128, 256, 512, dict(bm=128, bn=128, bk=256)),
    (8, 128, 128, dict(bm=8, bn=128, bk=128)),
]


def _setup(m, n, k, r, codebook, seed=0, dtype=jnp.float32):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (n, k), jnp.float32) * 0.02
    b, a = scaling.lords_init_from_weight(w, 128, rank=r)
    s = scaling.scale_matrix(b, a)
    codes = quantize.quantize_codes(w, s, codebook)
    qp = quantize.pack_codes(codes, codebook)
    return x, w, qp, b, a


@pytest.mark.parametrize("m,n,k,blocks", SHAPES)
@pytest.mark.parametrize("codebook", ["nf4", "nf3", "nf2"])
def test_lords_matmul_shapes(m, n, k, blocks, codebook):
    x, w, qp, b, a = _setup(m, n, k, 4, codebook)
    y_ref = ref.lords_matmul_ref(x, qp, b, a, codebook)
    y = ops.lords_matmul(x, qp, b, a, codebook, use_pallas=True,
                         interpret=True, **blocks)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lords_matmul_dtypes(dtype):
    x, w, qp, b, a = _setup(64, 128, 256, 4, "nf4", dtype=dtype)
    y_ref = ref.lords_matmul_ref(x, qp, b, a, "nf4")
    y = ops.lords_matmul(x, qp, b, a, "nf4", use_pallas=True, interpret=True,
                         bm=32, bn=64, bk=128)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("codebook", ["nf3", "nf2"])
@pytest.mark.parametrize("n,k", [(96, 160), (72, 328)])
def test_subbyte_dispatch_parity_non_tile_aligned(codebook, n, k):
    """Fused path (pad-to-tile + in-kernel sub-byte unpack) vs the ref
    oracle on shapes that divide neither the tile nor the lane width —
    forward at GEMV and GEMM widths, backward through x/b/a."""
    from repro.core import QuantSpec, init_quantized_linear
    from repro.kernels import dispatch

    spec = QuantSpec(method="lords", codebook=codebook, block_size=8,
                     rank=4, mode="peft")
    kw, kx = jax.random.split(jax.random.PRNGKey(n + k))
    w = jax.random.normal(kw, (n, k), jnp.float32) * 0.02
    params = init_quantized_linear(kw, n, k, spec, w)
    for m in (3, 16):
        x = jax.random.normal(kx, (m, k), jnp.float32)
        y_ref = dispatch.qmatmul(params, x, spec, n, k, backend="ref")
        y_int = dispatch.qmatmul(params, x, spec, n, k, backend="interpret")
        np.testing.assert_allclose(np.asarray(y_int), np.asarray(y_ref),
                                   rtol=3e-5, atol=3e-5)

    def loss(backend):
        def f(x_, b_, a_):
            p = {**params, "b": b_, "a": a_}
            return jnp.sum(dispatch.qmatmul(p, x_, spec, n, k,
                                            backend=backend) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(x, params["b"], params["a"])

    for g_ref, g_int in zip(loss("ref"), loss("interpret")):
        np.testing.assert_allclose(np.asarray(g_int), np.asarray(g_ref),
                                   rtol=2e-3, atol=2e-3)


def _int_code_matrices(fn, *args, n, k):
    """Every 2-D integer array of at least n·k elements in ``fn``'s jaxpr,
    nested jaxprs included: a dense unpacked (N, K) code matrix."""
    def int_avals(jx):
        for eqn in jx.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    if jnp.issubdtype(aval.dtype, jnp.integer):
                        yield aval
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from int_avals(sub)

    jaxpr = jax.make_jaxpr(fn)(*args)
    return [a_ for a_ in int_avals(jaxpr.jaxpr)
            if a_.ndim == 2 and a_.size >= n * k]


def test_subbyte_decode_has_no_dense_unpack_temporary():
    """The fused sub-byte path must unpack shift/mask *inside the tile*:
    no integer-typed (N, K) code array may appear anywhere in the jaxpr
    (that full-width temporary is exactly what true packing removes) — at
    a tile-aligned K, and through the dispatcher's own tiles at K that 512
    does not divide (minicpm3's 6400, internvl2's 4864), forward at GEMV
    and GEMM widths and the fused backward."""
    from repro.core import QuantSpec, init_quantized_linear
    from repro.kernels import dispatch

    m, n, k, r = 8, 128, 512, 4
    x, w, qp, b, a = _setup(m, n, k, r, "nf3")

    def fused(x, qp, b, a):
        return ops.lords_matmul(x, qp, b, a, "nf3", use_pallas=True,
                                interpret=True, bm=8, bn=64, bk=128)

    offenders = _int_code_matrices(fused, x, qp, b, a, n=n, k=k)
    assert not offenders, f"full-width unpack temporaries: {offenders}"

    spec = QuantSpec(method="lords", codebook="nf4", block_size=128,
                     rank=4, mode="peft")
    for k in (6400, 4864):
        params = init_quantized_linear(jax.random.PRNGKey(k), n, k, spec)

        def fwd_bwd(x_, b_, a_):
            p = {**params, "b": b_, "a": a_}
            return jnp.sum(dispatch.qmatmul(p, x_, spec, n, k,
                                            backend="interpret") ** 2)

        for m in (8, 16):
            x = jnp.ones((m, k), jnp.float32)
            fn = jax.value_and_grad(fwd_bwd, argnums=(0, 1, 2))
            offenders = _int_code_matrices(fn, x, params["b"], params["a"],
                                           n=n, k=k)
            assert not offenders, (f"K={k}, M={m}: full-width unpack "
                                   f"temporaries {offenders}")


@settings(max_examples=10, deadline=None)
@given(st.sampled_from([1, 2, 4, 8]), st.integers(0, 10_000),
       st.sampled_from(["nf4", "nf3", "nf2", "int8"]))
def test_lut_quantize_matches_oracle(rank, seed, codebook):
    _, w, _, b, a = _setup(8, 128, 256, rank, codebook, seed=seed)
    got = ops.lut_quantize(w, b, a, codebook, use_pallas=True, interpret=True,
                           bn=64, bk=128)
    want = ref.lut_quantize_ref(w, b, a, codebook)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bk", [64, 128, 256])
def test_block_matmul_both_tiling_regimes(bk):
    """bk >= block_size and bk < block_size paths must both be exact."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 512))
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 512)) * 0.02
    qb, sb = quantize.quantize_blockwise(w, 128, "nf4")
    y_ref = ref.block_matmul_ref(x, qb, sb, 128, "nf4")
    y = ops.block_matmul(x, qb, sb, 128, "nf4", use_pallas=True,
                         interpret=True, bm=32, bn=64, bk=bk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=3e-5, atol=3e-5)


def test_ops_dispatch_cpu_falls_back_to_ref():
    x, w, qp, b, a = _setup(16, 128, 128, 2, "nf4")
    y_auto = ops.lords_matmul(x, qp, b, a, "nf4")  # cpu -> ref path
    y_ref = ref.lords_matmul_ref(x, qp, b, a, "nf4")
    np.testing.assert_array_equal(np.asarray(y_auto), np.asarray(y_ref))


def test_kernel_matches_core_dequant_semantics():
    """ops.lords_matmul == x @ dequantize_weight(...)ᵀ from repro.core."""
    from repro.core import QuantSpec, dequantize_weight

    x, w, qp, b, a = _setup(32, 128, 256, 4, "nf4")
    spec = QuantSpec(method="lords", block_size=128, rank=4,
                     compute_dtype=jnp.float32)
    params = {"q": qp, "b": b, "a": a}
    w_hat = dequantize_weight(params, spec, 128, 256)
    y_core = x @ w_hat.T
    y_kern = ops.lords_matmul(x, qp, b, a, "nf4", use_pallas=True,
                              interpret=True, bm=32, bn=64, bk=128)
    np.testing.assert_allclose(np.asarray(y_core), np.asarray(y_kern),
                               rtol=3e-5, atol=3e-5)
