"""Weights drawn from the seed, at the shapes the program's own
``model_init`` gives (``jax.eval_shape``), in the dtypes it serves them in,
on the device, in one jitted call.

The large leaves (packed codes, embedding, head) come from a counter-based
hash of each element's index and a per-leaf salt drawn from the seed, so
every element is computed where it is stored: the call needs no
temporaries beyond its outputs (a ``jax.random`` draw of them all in one
program held 10.9 GB of temporaries at Qwen3-8B size, more than the chip
has beside the 6.2 GB of weights).

A LoRDS linear is drawn as its stored leaves, not as a dense weight that is
then quantized: codes uniform over the codebook (what NF quantiles give a
normal weight), and low-rank factors whose product ``S = B·A`` has a
magnitude near ``2.5 / sqrt(k_in)`` with rank-r variation — the absmax
block scale of a LeCun-normal weight is about that.

The sign of ``S`` is drawn at random per output row and per input column.
The NF4 levels average +0.023 under uniform codes, so with a positive
``S`` every linear carries a mean part ``0.023·S`` that maps the all-ones
direction onto itself; over 36 layers it takes over the hidden state, the
logits become ``±c·rowsum(head)`` and the served token is the table's
first or last by that sum, its sign set by a near-cancelling ``c``.  With
random signs the mean part of each linear is a rank-1 term between two
random directions, which no later layer feeds again.

A router (f32, experts × d) is normal with std ``1/sqrt(d)``, so the
routing logits of an RMS-normed row are about unit normal: neither uniform
nor one-hot.  Expert-stacked linears are quantized linears with a leading
expert axis, drawn by the same rule.

The reference gets the very same arrays; nothing the program computes
goes into them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["abstract_params", "draw", "flat", "seed_key"]

S0 = 2.5          # S ≈ S0 / sqrt(k_in)
EMBED_STD = 0.02  # embedding and head tables
NORM_JITTER = 0.1  # RMSNorm gains: 1 + NORM_JITTER · N(0, 1)


def seed_key(seed: int, stream: int):
    """A PRNG key for one purpose of one seed; any non-negative whole
    number (beyond 32 bits too) is a valid seed."""
    key = jax.random.PRNGKey(int(seed) & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (int(seed) >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def abstract_params(model_cfg):
    """The program's parameter tree as ShapeDtypeStructs."""
    from repro.models import model_init, split_tree

    tree = jax.eval_shape(lambda k: model_init(k, model_cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    return split_tree(tree)[0]


def _hash(key, shape):
    """A well-mixed uint32 per element: the 'lowbias32' integer hash of the
    element's linear index xor a salt drawn from ``key``."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for ax in reversed(range(len(shape))):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, ax) \
            * jnp.uint32(stride)
        stride *= shape[ax]
    if stride >= 2**32:
        raise ValueError(f"leaf of {stride} elements is too large to hash")
    x = idx ^ jax.random.bits(key, (), jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _codes(key, sds):
    """Uniform bytes: every 4-bit code uniform over the codebook."""
    return (_hash(key, sds.shape) >> 24).astype(jnp.uint8)


def _uniform(x):
    """uint32 -> float32 strictly inside (0, 1): 23 bits, so the largest,
    1 - 2**-24, is exact in float32 (24 bits would round to 1.0, whose
    normal quantile is infinite)."""
    return ((x >> 9).astype(jnp.float32) + 0.5) * 2.0**-23


def _normal(key, sds, std):
    u = _uniform(_hash(key, sds.shape))
    return (std * jax.scipy.special.ndtri(u)).astype(sds.dtype)


def _factors(key, b_sds, a_sds):
    """B (..., n, r) and A (..., r, k): one dominant rank, small others, so
    |S| = |B·A| ≈ S0/sqrt(k) · (1 + O(0.1)), with a random sign per output
    row (B's dominant column) and per input column (A's dominant row)."""
    k = a_sds.shape[-1]
    root = math.sqrt(S0 / math.sqrt(k))
    kb, ka, ksb, ksa = jax.random.split(key, 4)
    b = 0.05 * jax.random.normal(kb, b_sds.shape, jnp.float32)
    a = 0.05 * jax.random.normal(ka, a_sds.shape, jnp.float32)
    sb = jax.random.rademacher(ksb, b_sds.shape[:-1], jnp.float32)
    sa = jax.random.rademacher(ksa, a_sds.shape[:-2] + a_sds.shape[-1:],
                               jnp.float32)
    b = b.at[..., 0].add(sb)
    a = a.at[..., 0, :].add(sa)
    return (root * b).astype(b_sds.dtype), (root * a).astype(a_sds.dtype)


def _draw_tree(key, tree):
    if {"q", "b", "a"} <= set(tree):           # a quantized linear
        kq, kf = jax.random.split(key)
        extra = set(tree) - {"q", "b", "a"}
        if extra:
            raise ValueError(f"quantized linear with leaves {sorted(extra)}")
        b, a = _factors(kf, tree["b"], tree["a"])
        return {"q": _codes(kq, tree["q"]), "b": b, "a": a}
    out = {}
    names = sorted(tree)
    for name, k in zip(names, jax.random.split(key, len(names))):
        v = tree[name]
        if isinstance(v, dict):
            out[name] = _draw_tree(k, v)
        elif name in ("embed", "head"):
            out[name] = _normal(k, v, EMBED_STD)
        elif name == "router":
            out[name] = _normal(k, v, 1.0 / math.sqrt(v.shape[-1]))
        elif name in ("ln1", "ln2", "final_norm", "q_norm", "kv_norm"):
            out[name] = (1.0 + NORM_JITTER * jax.random.normal(
                k, v.shape, jnp.float32)).astype(v.dtype)
        else:
            raise ValueError(f"no drawing rule for parameter leaf {name!r}")
    return out


def draw(model_cfg, seed: int):
    """The whole parameter tree for ``seed``, on the default device."""
    shapes = abstract_params(model_cfg)
    params = jax.jit(lambda key: _draw_tree(key, shapes))(seed_key(seed, 0))
    return jax.block_until_ready(params)


def flat(tree, prefix: str = "") -> dict:
    """Nested dict -> {'layers/blk0/mixer/wq/q': array, ...}; None holes
    (the frozen half of a partitioned tree) are left out."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if v is None:
            continue
        if isinstance(v, dict):
            out.update(flat(v, name + "/"))
        else:
            out[name] = v
    return out
