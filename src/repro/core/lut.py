"""Quantization codebooks (look-up tables).

Every codebook is a sorted 1-D float32 array of discrete levels normalized to
[-1, 1].  Symmetric absmax scaling maps a weight block onto this range, so
``dequant = codebook[idx] * scale``.

NF4 follows QLoRA (Dettmers et al., 2023): quantiles of N(0,1) renormalized to
[-1, 1], with an exact zero.  NF2/NF3 are the natural 2-/3-bit analogues used
by the paper's mixed-precision low-bit configurations (Table 3: "3-bit" =
NF4 for the first 50% of layers, NF2 for the rest, etc.).
"""
from __future__ import annotations

import functools
import math

import jax.numpy as jnp
import numpy as np
from repro.core._norminv import ppf

__all__ = [
    "codebook",
    "codebook_bits",
    "CODEBOOKS",
    "midpoints",
    "static_levels",
    "static_midpoints",
    "mixed_precision_schedule",
    "realized_bits",
]


def _normal_quantile_levels(bits: int) -> np.ndarray:
    """NFk levels a la QLoRA: asymmetric quantile grid with an exact zero."""
    n = 2**bits
    # QLoRA/bitsandbytes construction: 2**(k-1)+1 non-negative quantiles
    # (including an exact 0) and 2**(k-1)-1 negative ones; the offset trick
    # avoids the infinite tails.  Matches the canonical NF4 table
    # [-1, -0.6962, ..., 0, 0.0796, ..., 0.7230, 1].
    offset = 0.5 * (1 / 32 + 1 / 30)
    pos = ppf(np.linspace(0.5, 1 - offset, n // 2 + 1))  # [0 ... max]
    neg = ppf(np.linspace(offset, 0.5, n // 2)[:-1])  # [min ... ) negative
    levels = np.concatenate([neg, pos])
    levels = levels / np.abs(levels).max()
    levels = np.sort(levels)
    # force an exact zero on the level closest to zero (QLoRA property)
    levels[np.argmin(np.abs(levels))] = 0.0
    return levels.astype(np.float32)


def _int_levels(bits: int) -> np.ndarray:
    """Symmetric INTk grid normalized to [-1, 1] (no exact -2^(k-1) asym)."""
    qmax = 2 ** (bits - 1) - 1
    return (np.arange(-qmax, qmax + 1) / qmax).astype(np.float32)


def _fp4_levels() -> np.ndarray:
    """FP4 (e2m1) value set, normalized to [-1, 1]."""
    vals = np.array(
        [0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0], dtype=np.float32
    )
    # e2m1 has ±0 sharing a value -> 15 distinct levels
    levels = np.sort(np.concatenate([-vals[1:], vals]))
    return (levels / np.abs(levels).max()).astype(np.float32)


@functools.lru_cache(maxsize=None)
def _build(name: str) -> np.ndarray:
    name = name.lower()
    if name == "nf4":
        return _normal_quantile_levels(4)
    if name == "nf3":
        return _normal_quantile_levels(3)
    if name == "nf2":
        # 2-bit normal-float: {-1, -1/3-ish, 0, +something} from quantiles
        return _normal_quantile_levels(2)
    if name == "int8":
        return _int_levels(8)
    if name == "int4":
        return _int_levels(4)
    if name == "int2":
        return _int_levels(2)
    if name == "fp4":
        return _fp4_levels()
    raise ValueError(f"unknown codebook {name!r}")


# name -> storage bits (packing density); NB int4 grid has 15 levels but
# still packs in 4 bits.
_BITS = {
    "nf4": 4,
    "nf3": 3,
    "nf2": 2,
    "int8": 8,
    "int4": 4,
    "int2": 2,
    "fp4": 4,
}
CODEBOOKS = tuple(_BITS)


def codebook(name: str) -> jnp.ndarray:
    """Sorted float32 levels in [-1, 1] for codebook ``name``."""
    return jnp.asarray(_build(name))


def codebook_bits(name: str) -> int:
    try:
        return _BITS[name.lower()]
    except KeyError:
        raise ValueError(
            f"unknown codebook {name!r}; available: {', '.join(CODEBOOKS)}"
        ) from None


def midpoints(name: str) -> jnp.ndarray:
    """Decision boundaries between adjacent levels (len = n_levels - 1)."""
    levels = _build(name)
    return jnp.asarray((levels[1:] + levels[:-1]) / 2)


def static_levels(name: str) -> tuple[float, ...]:
    """:func:`codebook` as Python floats (the exact f32 values): constants a
    kernel body can bake in while it is being traced."""
    return tuple(float(v) for v in _build(name))


def static_midpoints(name: str) -> tuple[float, ...]:
    """:func:`midpoints` as Python floats (exact f32 values)."""
    levels = _build(name)
    return tuple(float(v) for v in (levels[1:] + levels[:-1]) / 2)


def mixed_precision_schedule(
    num_layers: int, avg_bits: float, hi: str = "nf4", lo: str = "nf2"
) -> list[str]:
    """Paper Table 3 mixed-precision schedule.

    "3/2.5/2.25-bit configurations denote mixed-precision quantization, using
    NF4 for the first 50%/25%/12.5% of layers and NF2 for the remainder."
    Generalized: the fraction of hi-precision layers is chosen so the average
    bit width equals ``avg_bits`` given hi/lo bit widths.
    """
    b_hi, b_lo = codebook_bits(hi), codebook_bits(lo)
    if not (b_lo <= avg_bits <= b_hi):
        raise ValueError(f"avg_bits {avg_bits} outside [{b_lo}, {b_hi}]")
    frac_hi = (avg_bits - b_lo) / (b_hi - b_lo)
    # pick n_hi minimizing |realized − requested| average bits: plain
    # round(frac·n) can silently drift (e.g. 2.25-bit over 7 layers) and
    # rounds half-to-even, biasing small layer counts
    exact = frac_hi * num_layers
    n_hi = min(
        (int(math.floor(exact)), int(math.ceil(exact))),
        key=lambda c: (abs((c * b_hi + (num_layers - c) * b_lo) / num_layers
                           - avg_bits), c),
    )
    return [hi] * n_hi + [lo] * (num_layers - n_hi)


def realized_bits(schedule: list[str]) -> float:
    """Average storage bits/weight a mixed-precision schedule actually
    realizes (what ``bench_lowbit`` reports next to the requested width)."""
    if not schedule:
        return 0.0
    return sum(codebook_bits(c) for c in schedule) / len(schedule)
