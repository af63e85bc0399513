"""Training fast path: fused transposed/grad-reduction backward kernels.

Covers the PR-3 acceptance criteria: fused-vs-ref gradient parity (dx, dB,
dA, ds, dW) on non-tile-aligned shapes, vmap over MoE expert stacks, a
jaxpr check that no (N, K) dequantized-weight f32 temporary exists in any
lords/qat/peft backward, 3-step loss-decrease smokes for qat and peft
through the interpreter, and transposed-key autotune persistence.
"""
import json

import jax
import jax.extend.core as jax_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuantSpec, init_quantized_linear
from repro.kernels import dispatch, ref
from repro.kernels.dispatch import qmatmul
from repro.kernels.lords_grad import lords_grad_pallas
from repro.kernels.lords_matmul_t import lords_matmul_t_pallas

# deliberately NOT tile-aligned: M odd/small, N/K off the 128/256/512 grid
SHAPES = [(5, 96, 160), (33, 200, 96), (1, 130, 320)]


def _lords_setup(n, m, mode="peft", seed=0):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (n, m)) * 0.02
    spec = QuantSpec(method="lords", block_size=32, rank=3, mode=mode,
                     compute_dtype=jnp.float32)
    return init_quantized_linear(key, n, m, spec, w=w), spec


# ---------------------------------------------------------------------------
# kernel-level parity: transposed matmul + grad reduction vs the ref oracle
# ---------------------------------------------------------------------------


def test_transposed_kernel_matches_oracle_aligned():
    mtok, n, k = 16, 128, 256
    params, spec = _lords_setup(n, k)
    g = jax.random.normal(jax.random.PRNGKey(1), (mtok, n))
    dx_k = lords_matmul_t_pallas(g, params["q"], params["b"], params["a"],
                                 bm=8, bn=128, bk=128, interpret=True)
    dx_r = ref.lords_matmul_t_ref(g, params["q"], params["b"], params["a"])
    np.testing.assert_allclose(np.asarray(dx_k), np.asarray(dx_r),
                               rtol=3e-5, atol=3e-5)


def test_ops_wrappers_normalize_both_paths():
    """ops.lords_matmul_t / ops.lords_grad: kernel-path layout normalization
    (dbT transpose, da_part sum) must match the ref path's direct layout."""
    from repro.kernels import ops

    mtok, n, k = 16, 128, 256
    params, _ = _lords_setup(n, k, mode="qat")
    from repro.core.quantize import pack_codes, quantize_codes
    from repro.core.scaling import scale_matrix
    q = pack_codes(quantize_codes(
        params["w"], scale_matrix(params["b"], params["a"]), "nf4"), "nf4")
    g = jax.random.normal(jax.random.PRNGKey(15), (mtok, n))
    x = jax.random.normal(jax.random.PRNGKey(16), (mtok, k))
    kw = dict(interpret=True, bm=8, bn=128, bk=128)
    dx_k = ops.lords_matmul_t(g, q, params["b"], params["a"],
                              use_pallas=True, **kw)
    dx_r = ops.lords_matmul_t(g, q, params["b"], params["a"],
                              use_pallas=False)
    np.testing.assert_allclose(np.asarray(dx_k), np.asarray(dx_r),
                               rtol=3e-5, atol=3e-5)
    for w_arg in (None, params["w"]):
        g_k = ops.lords_grad(x, g, q, params["b"], params["a"], w=w_arg,
                             use_pallas=True, **kw)
        g_r = ops.lords_grad(x, g, q, params["b"], params["a"], w=w_arg,
                             use_pallas=False)
        assert len(g_k) == len(g_r) == (3 if w_arg is not None else 2)
        for name, gk, gr in zip(("db", "da", "dw"), g_k, g_r):
            np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                       rtol=3e-5, atol=3e-5, err_msg=name)


def test_grad_kernel_matches_oracle_aligned():
    mtok, n, k = 16, 128, 256
    params, spec = _lords_setup(n, k)
    g = jax.random.normal(jax.random.PRNGKey(2), (mtok, n))
    x = jax.random.normal(jax.random.PRNGKey(3), (mtok, k))
    dbt, da_part = lords_grad_pallas(x, g, params["q"], params["b"],
                                     params["a"], bm=8, bn=128, bk=128,
                                     interpret=True)
    _, db_r, da_r = ref.lords_grads_ref(g, x, params["q"], params["b"],
                                        params["a"])
    np.testing.assert_allclose(np.asarray(dbt.T), np.asarray(db_r),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.asarray(da_part.sum(0)), np.asarray(da_r),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# dispatch-level gradient parity on non-tile-aligned shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mtok,n,m", SHAPES)
def test_peft_bwd_parity_nonaligned(mtok, n, m):
    """dx, dB, dA: fused interpret backward == ref == legacy dense."""
    params, spec = _lords_setup(n, m, mode="peft")
    x = jax.random.normal(jax.random.PRNGKey(4), (mtok, m))

    def loss(t, xx, bk):
        p = dict(params, b=t[0], a=t[1])
        return jnp.sum(qmatmul(p, xx, spec, n, m, backend=bk) ** 2)

    t0 = (params["b"], params["a"])
    for bk in ("interpret", "ref"):
        g_f = jax.grad(loss)(t0, x, bk)
        g_d = jax.grad(loss)(t0, x, "dense")
        for name, gf, gd in zip("ba", g_f, g_d):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=f"{bk} d{name}")
        gx_f = jax.grad(loss, argnums=1)(t0, x, bk)
        gx_d = jax.grad(loss, argnums=1)(t0, x, "dense")
        np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_d),
                                   rtol=1e-4, atol=1e-5, err_msg=f"{bk} dx")


@pytest.mark.parametrize("mtok,n,m", SHAPES)
def test_qat_bwd_parity_nonaligned(mtok, n, m):
    """dx, dW, dB, dA: fused STE backward (Eq. 4/5) == dense autodiff."""
    params, spec = _lords_setup(n, m, mode="qat")
    x = jax.random.normal(jax.random.PRNGKey(5), (mtok, m))

    def loss(t, xx, bk):
        p = dict(params, w=t[0], b=t[1], a=t[2])
        return jnp.sum(qmatmul(p, xx, spec, n, m, backend=bk) ** 2)

    t0 = (params["w"], params["b"], params["a"])
    g_f = jax.grad(loss)(t0, x, "interpret")
    g_d = jax.grad(loss)(t0, x, "dense")
    for name, gf, gd in zip("wba", g_f, g_d):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("mtok,n,m,bs", [(5, 96, 160, 32), (7, 64, 192, 96)])
def test_block_bwd_parity(mtok, n, m, bs):
    """ds_blk + dx parity, incl. a block spanning multiple k tiles."""
    key = jax.random.PRNGKey(6)
    spec = QuantSpec(method="blockwise", block_size=bs,
                     compute_dtype=jnp.float32)
    params = init_quantized_linear(key, n, m, spec,
                                   w=jax.random.normal(key, (n, m)) * 0.02)
    x = jax.random.normal(jax.random.PRNGKey(7), (mtok, m))

    def loss(s, xx, bk):
        return jnp.sum(qmatmul(dict(params, s_blk=s), xx, spec, n, m,
                               backend=bk) ** 2)

    gs_f = jax.grad(loss)(params["s_blk"], x, "interpret")
    gs_d = jax.grad(loss)(params["s_blk"], x, "dense")
    np.testing.assert_allclose(np.asarray(gs_f), np.asarray(gs_d),
                               rtol=1e-4, atol=1e-5)
    gx_f = jax.grad(loss, argnums=1)(params["s_blk"], x, "interpret")
    gx_d = jax.grad(loss, argnums=1)(params["s_blk"], x, "dense")
    np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_d),
                               rtol=1e-4, atol=1e-5)


def test_vmap_moe_expert_stack_grads():
    """Backward through a vmapped expert stack (the MoE training path)."""
    spec = QuantSpec(method="lords", block_size=32, rank=2, mode="peft",
                     compute_dtype=jnp.float32)
    e, n, m = 3, 64, 96
    keys = jax.random.split(jax.random.PRNGKey(8), e)
    stack = jax.vmap(lambda k: init_quantized_linear(k, n, m, spec))(keys)
    xd = jax.random.normal(jax.random.PRNGKey(9), (e, 16, m))

    def loss(ba, bk):
        y = jax.vmap(
            lambda bb, aa, q, xe: qmatmul({"q": q, "b": bb, "a": aa}, xe,
                                          spec, n, m, backend=bk)
        )(ba[0], ba[1], stack["q"], xd)
        return jnp.sum(y ** 2)

    g_f = jax.grad(loss)((stack["b"], stack["a"]), "interpret")
    g_d = jax.grad(loss)((stack["b"], stack["a"]), "dense")
    for name, gf, gd in zip("ba", g_f, g_d):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-5, err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# no (N, K) f32 dequantized-weight temporary in the fused backward (jaxpr)
# ---------------------------------------------------------------------------

# primitives allowed to produce (>=N, >=K)-shaped float arrays in the fused
# path: kernel launches (their tile-level internals live in VMEM, not HBM),
# operand padding, slicing kernel outputs (the QAT dW *parameter gradient*
# flows through these), and call boundaries (jit / pjit, by JAX version:
# pass-through — their bodies are walked separately).  Anything else —
# dot_general for S=B·A, gather for lut[Q], mul for vals⊙S — is dense-path
# dequantization.
_ALLOWED = {"pallas_call", "pad", "slice", "dynamic_slice", "squeeze",
            "reshape", "copy", "transpose", "pjit", "jit"}


def _nk_float_eqns(fn, *args, n, k):
    """(primitive, shape) of every eqn output with a (>=n, >=k) float shape,
    walking nested jaxprs but not into pallas_call kernel bodies."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                aval = v.aval
                shape = getattr(aval, "shape", ())
                if (len(shape) == 2 and shape[0] >= n and shape[1] >= k
                        and jnp.issubdtype(aval.dtype, jnp.floating)):
                    found.append((eqn.primitive.name, shape))
            if eqn.primitive.name == "pallas_call":
                continue
            for val in eqn.params.values():
                for sub in _subjaxprs(val):
                    walk(sub)

    def _subjaxprs(val):
        if isinstance(val, jax_core.ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, jax_core.Jaxpr):
            yield val
        elif isinstance(val, (tuple, list)):
            for v in val:
                yield from _subjaxprs(v)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("mode", ["peft", "qat"])
def test_no_dense_weight_temp_in_fused_bwd(mode):
    n, m = 96, 160
    params, spec = _lords_setup(n, m, mode=mode)
    x = jax.random.normal(jax.random.PRNGKey(10), (5, m))
    keys = ("w", "b", "a") if mode == "qat" else ("b", "a")

    def make_loss(bk):
        def loss(t):
            return jnp.sum(
                qmatmul(dict(params, **dict(zip(keys, t))), x, spec, n, m,
                        backend=bk) ** 2)
        return loss

    t0 = tuple(params[kk] for kk in keys)
    fused = _nk_float_eqns(jax.grad(make_loss("interpret")), t0, n=n, k=m)
    bad = [f for f in fused if f[0] not in _ALLOWED]
    assert not bad, f"dense (N,K) temporaries in fused {mode} bwd: {bad}"
    # sanity: the checker does flag the legacy dequantize-then-einsum path
    dense = _nk_float_eqns(jax.grad(make_loss("dense")), t0, n=n, k=m)
    assert len([f for f in dense if f[0] not in _ALLOWED]) >= 3


# ---------------------------------------------------------------------------
# 3-step loss-decrease smokes through the interpreter (fused fwd + bwd)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["peft", "qat"])
def test_three_step_loss_decrease_interpret(mode):
    n, m = 64, 96
    params, spec = _lords_setup(n, m, mode=mode, seed=11)
    x = jax.random.normal(jax.random.PRNGKey(12), (32, m))
    y = jax.random.normal(jax.random.PRNGKey(13), (32, n)) * 0.1
    keys = ("w", "b", "a") if mode == "qat" else ("b", "a")
    t = {kk: params[kk] for kk in keys}

    def loss_fn(t):
        p = dict(params, **t)
        return jnp.mean((qmatmul(p, x, spec, n, m, backend="interpret") - y)
                        ** 2)

    vg = jax.jit(jax.value_and_grad(loss_fn))
    losses = []
    for _ in range(3):
        l, g = vg(t)
        losses.append(float(l))
        t = jax.tree.map(lambda p, gg: p - 0.05 * gg, t, g)
    losses.append(float(vg(t)[0]))
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


# ---------------------------------------------------------------------------
# transposed-key autotune: registration, numerics, persistence
# ---------------------------------------------------------------------------


def test_bwd_autotune_registers_and_persists(tmp_path, monkeypatch):
    cache = tmp_path / "tiles.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    n, m = 96, 160
    params, spec = _lords_setup(n, m, mode="peft")
    x = jax.random.normal(jax.random.PRNGKey(14), (5, m))
    best, timings = dispatch.autotune_qmatmul_bwd(
        params, x, spec, n, m, backend="interpret",
        candidates=[(8, 128, 256), (8, 128, 512)], iters=1)
    assert best in timings and len(timings) >= 1
    assert dispatch.lookup_tiles("lords_t", 5, n, m, spec.codebook,
                                 jnp.float32) == best
    data = json.loads(cache.read_text())
    assert any(e["key"][0] == "lords_t" for e in data["entries"])
    # backward with the registered transposed tiles still matches the oracle
    def loss(t, bk):
        p = dict(params, b=t[0], a=t[1])
        return jnp.sum(qmatmul(p, x, spec, n, m, backend=bk) ** 2)
    g_f = jax.grad(loss)((params["b"], params["a"]), "interpret")
    g_r = jax.grad(loss)((params["b"], params["a"]), "ref")
    for gf, gr in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-4, atol=1e-5)
