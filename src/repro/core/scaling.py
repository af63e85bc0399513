"""Scaling-matrix construction: block-wise scales and the LoRDS S = B·A init.

Conventions (paper §3.1):
  * weight ``W ∈ R^{n×m}`` (out_features × in_features),
  * blocks are contiguous runs of ``block_size`` elements along the *rows*
    (the in-features axis), matching bitsandbytes / QLoRA flattening,
  * the global scaling matrix ``S ∈ R^{n×m}`` repeats each block scale:
    ``S = s ⊗ 1_{1×B}`` with ``s ∈ R^{n×(m/B)}`` → ``rank(S) ≤ m/B``.

The LoRDS initialization (paper Eq. 3) truncates the SVD of S:
  ``S ≈ (U_r Σ_r^{1/2})(Σ_r^{1/2} V_rᵀ) = B·A``
with the parameter-parity rank ``r = ⌊ n·m / (B·(n+m)) ⌋`` (Appendix A).
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = [
    "parity_rank",
    "blockwise_scales",
    "eff_block",
    "expand_block_scales",
    "svd_init",
    "svd_init_blocks",
    "lords_init_from_weight",
    "scale_matrix",
    "clamp_scale",
    "SCALE_EPS",
]

# Scales must stay away from zero: the quantization step divides by S.
SCALE_EPS = 1e-8


def clamp_scale(s: jnp.ndarray, eps: float = SCALE_EPS) -> jnp.ndarray:
    """|S| >= eps, sign-preserving — THE clamp rule, shared by every Pallas
    kernel body, the ref oracles, and :func:`scale_matrix`.  The backward
    mask is its boundary (``|S| >= eps``); keeping both rules in one module
    is what guarantees forward/backward consistency."""
    sign = jnp.where(s >= 0, 1.0, -1.0).astype(s.dtype)
    return jnp.where(jnp.abs(s) < eps, sign * eps, s)


def parity_rank(n: int, m: int, block_size: int, extra_rank: int = 0) -> int:
    """r = floor(n*m / (B*(n+m))) (+ r_q for the parameter-aligned LoRDS†)."""
    r = (n * m) // (block_size * (n + m)) + extra_rank
    return max(int(r), 1)


def eff_block(m: int, block_size: int) -> int:
    """Effective block size: clamped to the row length (tiny matrices)."""
    return min(block_size, m)


def blockwise_scales(w: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """Symmetric absmax block scales, shape (n, m // block_size).

    Each scale maps its block onto [-1, 1] so codebook levels (normalized to
    [-1, 1]) dequantize as ``level * scale``.
    """
    n, m = w.shape
    block_size = eff_block(m, block_size)
    if m % block_size:
        raise ValueError(f"in-features {m} not divisible by block {block_size}")
    blocks = w.reshape(n, m // block_size, block_size)
    return jnp.maximum(jnp.max(jnp.abs(blocks), axis=-1), SCALE_EPS)


def expand_block_scales(s: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """(n, m/B) block scales -> dense (n, m) piecewise-constant S."""
    return jnp.repeat(s, block_size, axis=1)


def svd_init(s_dense: jnp.ndarray, rank: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Truncated-SVD factorization S ≈ B·A with balanced sqrt(Σ) split."""
    u, sig, vt = jnp.linalg.svd(s_dense, full_matrices=False)
    r = min(rank, sig.shape[0])
    root = jnp.sqrt(sig[:r])
    b = u[:, :r] * root[None, :]
    a = root[:, None] * vt[:r, :]
    return b, a


def svd_init_blocks(
    s_blk: jnp.ndarray, block_size: int, rank: int,
    col_scale: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`svd_init` of ``S = expand(s_blk) / col_scale`` without forming
    or decomposing the dense (n, m) S.

    Row i of the block expansion ``E·diag(1/c)`` is nonzero on block i
    only, so its rows are orthogonal: ``E·diag(1/c) = Λ·Q`` with Λ the row
    norms and Q orthonormal rows.  Hence ``S = (s_blk·Λ)·Q`` and the SVD of
    the small (n, m/B) matrix ``s_blk·Λ`` gives S's: same U and Σ, V = Qᵀ·V'.
    Exact for ``rank <= m/B`` (rank(S) <= m/B); an SVD whose smaller side is
    m/B instead of min(n, m) is what makes the full-width init fast."""
    n, nb = s_blk.shape
    inv_c = (jnp.ones((nb * block_size,), s_blk.dtype) if col_scale is None
             else 1.0 / col_scale)
    rows = inv_c.reshape(nb, block_size)
    lam = jnp.sqrt(jnp.sum(rows * rows, axis=1))             # (nb,)
    u, sig, vt = jnp.linalg.svd(s_blk * lam[None, :], full_matrices=False)
    r = min(rank, sig.shape[0])
    root = jnp.sqrt(sig[:r])
    b = u[:, :r] * root[None, :]
    q = rows / lam[:, None]                                  # Q's row blocks
    a = (root[:, None, None] * vt[:r, :, None] * q[None]).reshape(r, -1)
    return b, a


def lords_init_from_weight(
    w: jnp.ndarray,
    block_size: int,
    rank: int | None = None,
    extra_rank: int = 0,
    channel_scale: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Full LoRDS init: block scales -> S -> truncated SVD -> (B, A).

    ``channel_scale`` (m,): SmoothQuant-style per-input-channel smoothing
    scales c_j, folded into the init — block scales are computed on the
    smoothed weight W ⊙ c and the dense S is divided back by c, so
    quantizing W against this S is exactly quantizing W ⊙ c against its own
    block scales.  Because S is element-wise the smoothing is free: no
    runtime transform, no extra stored tensors, and refinement can move off
    the smoothed manifold if the data prefers.
    """
    n, m = w.shape
    if rank is None:
        rank = parity_rank(n, m, block_size, extra_rank)
    block_size = eff_block(m, block_size)
    pad = -m % block_size
    if pad:
        # a partial last block (DeepSeek-V2-Lite's 10944 at 128): scales
        # from the real columns zero-padded to whole blocks; the padding's
        # columns of A are dropped
        cs = None if channel_scale is None else jnp.pad(
            channel_scale, (0, pad), constant_values=1.0)
        b, a = lords_init_from_weight(jnp.pad(w, ((0, 0), (0, pad))),
                                      block_size, rank=rank,
                                      channel_scale=cs)
        return b, a[:, :m]
    c = None
    if channel_scale is not None:
        c = jnp.maximum(jnp.abs(channel_scale.astype(w.dtype)), SCALE_EPS)
    s_blk = blockwise_scales(w if c is None else w * c[None, :], block_size)
    if rank <= s_blk.shape[1]:  # within rank(S): the factored SVD is exact
        return svd_init_blocks(s_blk, block_size, rank, c)
    # components past rank(S) span its null space, which only the dense
    # SVD defines
    s = expand_block_scales(s_blk, block_size)
    return svd_init(s if c is None else s / c[None, :], rank)


def scale_matrix(b: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """S = B·A, clamped away from zero (sign-preserving)."""
    return clamp_scale(b @ a)
