"""Shared model plumbing: param leaves with logical axes, norms, RoPE,
activation-sharding constraints.

Params are plain nested dicts whose leaves are :class:`P` — an array (or
ShapeDtypeStruct under ``jax.eval_shape``) tagged with *logical axis names*.
``split_tree`` separates values from axes so the distributed layer can build
PartitionSpecs without introspecting module code.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "P",
    "f32_einsum",
    "split_tree",
    "tree_axes",
    "qlinear_init",
    "qlinear_apply",
    "dense_init",
    "rmsnorm_init",
    "rmsnorm",
    "rope_freqs",
    "apply_rope",
    "shard",
    "activation_rules",
    "current_rules",
    "stack_periods",
    "kv_quantize",
    "kv_dequantize",
]


def f32_einsum(subscripts, *args):
    """einsum with f32 accumulation.

    TPU path (default): bf16 operands + preferred_element_type=f32 — native
    MXU mixed precision, no operand upcasts in HBM.
    CPU-execution path (REPRO_CPU_EXEC=1, set by tests/drivers/benchmarks):
    upcast operands — XLA:CPU cannot *execute* BF16×BF16→F32 dots.  The
    dry-run compiles on CPU but never executes, so it keeps the TPU form.
    """
    import os

    if os.environ.get("REPRO_CPU_EXEC") == "1":
        args = tuple(a.astype(jnp.float32) for a in args)
        return jnp.einsum(subscripts, *args)
    return jnp.einsum(subscripts, *args,
                      preferred_element_type=jnp.float32)


class P(NamedTuple):
    """A parameter leaf: array + logical axis names (one per dim)."""

    value: Any
    axes: tuple

    # make jax.tree happy if leaves leak through untyped paths
    def __repr__(self):
        shape = getattr(self.value, "shape", None)
        return f"P(shape={shape}, axes={self.axes})"


jax.tree_util.register_pytree_node(
    P, lambda p: ((p.value,), p.axes), lambda axes, v: P(v[0], axes)
)


def _is_p(x):
    return isinstance(x, P)


def split_tree(tree):
    """tree-of-P -> (tree of arrays, tree of axis tuples)."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=_is_p)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=_is_p)
    return values, axes


def tree_axes(tree):
    return jax.tree.map(lambda p: p.axes, tree, is_leaf=_is_p)


def stack_periods(period_trees: list):
    """Stack per-period param trees along a new leading 'layers' axis.

    Consumes ``period_trees`` (the list is emptied): each leaf's per-period
    arrays are dropped as soon as its stacked copy exists, so stacking a
    full-size model peaks at one model plus one leaf, not two models."""
    flat, treedef = [], None
    while period_trees:
        leaves, treedef = jax.tree.flatten(period_trees.pop(0),
                                           is_leaf=_is_p)
        flat.append(leaves)
    stacked = []
    for i, first in enumerate(flat[0]):
        vals = [leaves[i].value for leaves in flat]
        for leaves in flat:
            leaves[i] = None
        stacked.append(P(jnp.stack(vals, axis=0), ("layers",) + first.axes))
        del vals
    return jax.tree.unflatten(treedef, stacked)


# ---------------------------------------------------------------------------
# Quantized + dense linears as P-trees
# ---------------------------------------------------------------------------


@functools.cache
def _jitted_linear_init():
    """One compiled init per distinct (n, m, spec, bias): a full-width model
    re-runs a handful of programs instead of dispatching every draw, SVD
    and pack of its hundreds of linears op by op."""
    from repro.core import init_quantized_linear

    return jax.jit(init_quantized_linear,
                   static_argnames=("n", "m", "spec", "use_bias"))


def qlinear_init(key, n, m, quant_spec, out_axis, in_axis, w=None,
                 use_bias=False):
    """Quantized linear (repro.core) wrapped in P leaves with logical axes."""
    from repro.core import linear_param_specs

    params = _jitted_linear_init()(key, n=n, m=m, spec=quant_spec, w=w,
                                   use_bias=use_bias)
    axes = linear_param_specs(quant_spec, out_axis, in_axis, use_bias=use_bias)
    return {k: P(v, axes[k]) for k, v in params.items()}


def qlinear_apply(params, x, quant_spec, n, m):
    """Quantized matmul through the unified kernel-dispatch layer."""
    from repro.kernels.dispatch import qmatmul

    return qmatmul(params, x, quant_spec, n, m)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _scaled_normal(key, shape, dtype, scale):
    """One program: the f32 draw, its scaled copy and the cast fuse, so a
    (vocab, d_model) bf16 table costs its own bytes while it is drawn, not
    five times them (each op run eagerly holds its f32 input and output)."""
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense_init(key, shape, axes, dtype=jnp.bfloat16, scale=None):
    """Unquantized dense weight (router, embeddings, conv, gates...)."""
    if scale is None:
        scale = 1.0 / jnp.sqrt(shape[-1])
    return P(_scaled_normal(key, tuple(shape), dtype, scale), axes)


# ---------------------------------------------------------------------------
# Quantized KV-cache storage (per-vector symmetric int8)
# ---------------------------------------------------------------------------

_KV_EPS = 1e-8  # all-zero vectors (cache padding) quantize to scale eps


def kv_quantize(x, axis: int = -1):
    """Symmetric int8 over ``axis``: returns (codes int8, scales f32).

    The scale tensor drops ``axis`` (one f32 per quantized vector — for a
    (b, s, nkv, hd) cache with axis=-1 that is per-token-per-head, the
    'per-head scales' layout the decode roofline wants: hd int8 + 4 bytes
    instead of hd bf16 per head-token).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, _KV_EPS) / 127.0
    codes = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return codes, jnp.squeeze(scale, axis=axis)


def kv_dequantize(codes, scale, axis: int = -1, dtype=jnp.bfloat16):
    """Inverse of :func:`kv_quantize` (codes ⊙ broadcast scales)."""
    return (codes.astype(jnp.float32)
            * jnp.expand_dims(scale, axis)).astype(dtype)


# ---------------------------------------------------------------------------
# Norms / RoPE
# ---------------------------------------------------------------------------


def rmsnorm_init(d, axis="embed"):
    return P(jnp.ones((d,), jnp.float32), (axis,))


def rmsnorm(g, x, eps=1e-5):
    import os

    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    if os.environ.get("REPRO_BF16_ELEMWISE") == "1":
        # perf mode: variance in f32, application in the compute dtype —
        # halves the (b,s,d)-sized elementwise traffic of every norm
        inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
        return x * inv * g.astype(x.dtype)
    return (g * xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def rope_freqs(head_dim, theta=10000.0):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))
    return inv  # (head_dim/2,)


def apply_rope(x, positions, theta=10000.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32."""
    import os

    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., s, hd/2)
    dt = x.dtype if os.environ.get("REPRO_BF16_ELEMWISE") == "1" else jnp.float32
    cos = jnp.cos(ang)[..., None, :].astype(dt)
    sin = jnp.sin(ang)[..., None, :].astype(dt)
    x1, x2 = jnp.split(x.astype(dt), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activation sharding constraints (rules are ambient, set by the launcher)
# ---------------------------------------------------------------------------

_TLS = threading.local()


@contextlib.contextmanager
def activation_rules(rules: dict | None):
    """Context manager installing logical->mesh rules for ``shard``."""
    prev = getattr(_TLS, "rules", None)
    _TLS.rules = rules
    try:
        yield
    finally:
        _TLS.rules = prev


def current_rules() -> dict | None:
    """The ambient activation rules (installed by the launcher's step fn),
    or None outside any :func:`activation_rules` scope.  The mesh rides
    along under the ``"__mesh__"`` key — the supported way for model code
    (e.g. the shard_map MoE) to reach the active mesh."""
    return getattr(_TLS, "rules", None)


def shard(x, *axes):
    """with_sharding_constraint by logical axis names; no-op without rules."""
    rules = current_rules()
    if rules is None:
        return x
    mesh = rules.get("__mesh__")
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    spec, used = [], set()
    for dim, name in zip(x.shape, axes):
        mesh_axes = rules.get(name)
        if mesh_axes is None:
            spec.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        size = 1
        ok = []
        for ax in mesh_axes:
            if ax in used or ax not in mesh.shape:
                continue
            size *= mesh.shape[ax]
            ok.append(ax)
        if ok and size > 1 and dim % size == 0:
            spec.append(tuple(ok) if len(ok) > 1 else ok[0])
            used.update(ok)
        else:
            spec.append(None)
    sharding = NamedSharding(mesh, PartitionSpec(*spec))
    return jax.lax.with_sharding_constraint(x, sharding)
