"""Unified, differentiable kernel dispatch for every quantized matmul.

``qmatmul(params, x, spec, n, m)`` is the single entry point all quantized
linears go through (core/lords, models/*, launch/serve, benchmarks).  It
replaces the old "always materialize Ŵ, then einsum" forward with a
QuantSpec-aware dispatch over four backends:

  * ``pallas``    — fused Pallas TPU kernels (``lords_matmul``,
                    ``block_matmul``, ``lut_quantize``): the low-rank scale
                    product S = B·A rides along with each weight tile, so Ŵ
                    never exists in HBM (paper §4.4 serving claim).
  * ``interpret`` — the same kernel bodies under the Pallas interpreter, so
                    CPU CI executes the real fused code paths.
  * ``ref``       — the pure-jnp oracles from :mod:`repro.kernels.ref`
                    (default off-TPU: numerically identical contract).
  * ``dense``     — the legacy dequantize-then-einsum path, kept as the
                    universal fallback (blockwise QAT, AWQ-smoothed weights,
                    any method/mode combination the fused kernels don't cover).

Selection: explicit ``backend=`` argument > :func:`backend_scope` context >
``REPRO_KERNEL_BACKEND`` env > ``REPRO_INTERPRET_KERNELS=1`` env (tests/CI) >
platform default (pallas on TPU, ref elsewhere).

Padding: the raw Pallas kernels require tile-divisible (M, N, K) and raise
otherwise.  The dispatcher instead zero-pads every operand up to the active
tile multiples and slices the result — K-padding is exact because x is
zero-padded along K, and padded N rows / M columns are sliced off.  Padded
scale entries hit the kernels' |S| >= eps clamp, never a divide-by-zero.

Differentiability: fused lords forwards carry ``jax.custom_vjp``s —
``peft`` mode backpropagates to (B, A) through the multiplicative scale
(the clamp-masked ∂S rule autodiff would produce on the dense path), and
``qat`` mode implements the paper's STE cotangents (Eq. 4/5: ∇W = ∂L/∂Ŵ,
∇S = ∂L/∂Ŵ ⊙ (Q − W⊘S)).  On the fused backends the *backward* is fused
too: dx runs the transposed dequant-matmul kernel
(:mod:`repro.kernels.lords_matmul_t`) and the parameter gradients the
tiled grad-reduction kernel (:mod:`repro.kernels.lords_grad`), so neither
the forward nor the backward ever materializes an (N, K) f32 Ŵ (or ∂S)
temporary — training costs packed-weight bandwidth, not dense bandwidth.
On ``ref``/``dense`` backends the backward runs the single dense-math
oracle :func:`repro.kernels.ref.lords_grads_ref` (one dequant, shared
Eq. 4/5 / chain-rule helpers from ``core.qat`` / ``core.peft``).
Backward tile choices use the *transposed* autotune keys (``lords_t`` /
``blockwise_t``, tuned by ``autotune_qmatmul_bwd``); the ``tiles=``
argument only pins the forward.

Decode fast path: fused lords forwards with M ≤ 8 flattened tokens route to
the weight-stationary GEMV kernel (:mod:`repro.kernels.lords_decode`) —
weights stream exactly once per call, the memory-roofline minimum for
autoregressive decoding.  The routing is by trace-time shape, so a jitted
serve step picks the decode kernel automatically.

Sharded execution: inside a ``shard_scope(mesh)`` the fused lords /
blockwise paths run data+tensor-parallel over the mesh via ``shard_map``:
the packed codes (and the row dim of B / the QAT master W / the block
scales) shard over 'model' while the rank-r A factor stays replicated —
the codes-shard / factors-replicate layout the sharding rules in
:mod:`repro.distributed.sharding` assign to every quantized linear — and
the flattened token dim shards over the remaining (data/pod) mesh axes
when it divides them.  The custom VJPs stay fused per shard and
psum-reduce exactly the cross-shard cotangents (dx over 'model', dB/dW/
ds_blk over the data axes, dA over both), so a data+tensor-parallel
QAT/PEFT step never materializes Ŵ either.  Layers whose out-dim does not
divide the model axis fall back to the unsharded path (mirroring
``resolve_spec``'s divisibility drops), as does the ``dense`` backend
(GSPMD partitions its einsum directly).

Autotuning: per-(method, M-bucket, N, K, codebook, dtype) tile choices live
in a small in-process table.  ``autotune_qmatmul`` times candidate tilings
through the public entry point and registers the winner; subsequent
``qmatmul`` traces consult the table (lookups happen at trace time).  Set
``REPRO_AUTOTUNE_CACHE=/path/to/table.json`` to persist the table across
processes: it is loaded on import and saved after every successful
``autotune_qmatmul``, so benchmark-found tiles survive into serving runs.
"""
from __future__ import annotations

import contextlib
import functools
import json
import math
import os
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core.quantize import repack_width
from repro.kernels import ref
from repro.kernels.attn_decode import (
    DECODE_ROWS,
    attn_decode_gqa_paged_pallas,
    attn_decode_gqa_pallas,
    attn_decode_mla_paged_pallas,
    attn_decode_mla_pallas,
)
from repro.kernels.attn_prefill import attn_prefill_pallas
from repro.kernels.block_matmul import block_matmul_pallas
from repro.kernels.lords_decode import DECODE_M_MAX, lords_decode_pallas
from repro.kernels.lords_grad import block_grad_pallas, lords_grad_pallas
from repro.kernels.lords_matmul import lords_matmul_pallas
from repro.kernels.lords_matmul_t import (
    block_matmul_t_pallas,
    lords_matmul_t_pallas,
)
from repro.kernels.lut_quantize import lut_quantize_pallas

__all__ = [
    "BACKENDS",
    "qmatmul",
    "qattention",
    "default_backend",
    "fused_backend_active",
    "backend_scope",
    "shard_scope",
    "shard_info",
    "tile_for",
    "attn_tile_for",
    "lookup_tiles",
    "register_tiles",
    "autotune_qmatmul",
    "autotune_qmatmul_bwd",
    "autotune_qattention",
    "autotune_table",
    "load_autotune_table",
    "save_autotune_table",
]

BACKENDS = ("pallas", "interpret", "ref", "dense")
_FUSED = ("pallas", "interpret")

_TLS = threading.local()


# ---------------------------------------------------------------------------
# Backend selection
# ---------------------------------------------------------------------------


def default_backend() -> str:
    """Resolve the active backend (see module docstring for precedence)."""
    scoped = getattr(_TLS, "backend", None)
    forced = scoped or os.environ.get("REPRO_KERNEL_BACKEND")
    if forced:
        if forced not in BACKENDS:
            raise ValueError(
                f"unknown kernel backend {forced!r}; expected one of {BACKENDS}"
            )
        return forced
    if os.environ.get("REPRO_INTERPRET_KERNELS") == "1":
        return "interpret"
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@contextlib.contextmanager
def backend_scope(backend: str | None):
    """Pin the dispatch backend for everything traced inside the scope.

    ``None`` leaves the ambient selection untouched (so launchers can thread
    an optional CLI flag straight through).
    """
    if backend is not None and backend not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r}; expected one of {BACKENDS}"
        )
    prev = getattr(_TLS, "backend", None)
    _TLS.backend = backend if backend is not None else prev
    try:
        yield
    finally:
        _TLS.backend = prev


def _resolve(backend: str | None) -> str:
    return backend if backend is not None else default_backend()


def fused_backend_active(backend: str | None = None) -> bool:
    """Whether the resolved backend runs the fused Pallas kernel bodies —
    the single routing predicate model code and plan metadata share, so a
    backend added to ``_FUSED`` can never leave them disagreeing."""
    return _resolve(backend) in _FUSED


# ---------------------------------------------------------------------------
# Tensor-parallel scope (shard_map over the mesh's model axis)
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def shard_scope(mesh, axis: str = "model"):
    """Run fused qmatmuls traced inside tensor-parallel over ``mesh``.

    Packed codes / B rows / the QAT master W shard over ``axis``; A stays
    replicated; the custom VJPs psum dx and dA across ``axis``.  Unlike
    :func:`backend_scope`, ``mesh=None`` (or a mesh where ``axis`` has size
    1) explicitly *disables* sharded dispatch inside the scope — the form
    the MoE shard_map bodies use to stop fused matmuls from opening a
    nested shard_map.
    """
    prev = getattr(_TLS, "shard", None)
    active = mesh is not None and dict(mesh.shape).get(axis, 1) > 1
    _TLS.shard = (mesh, axis) if active else None
    try:
        yield
    finally:
        _TLS.shard = prev


def shard_info() -> tuple | None:
    """The active (mesh, model-axis) pair, or None outside any shard_scope."""
    return getattr(_TLS, "shard", None)


def _tp_shard(backend: str, n: int) -> tuple | None:
    """Resolve the tensor-parallel route for an (N, K) quantized linear.

    Returns (mesh, axis) when a shard scope is active, the backend has a
    fused/ref per-shard body, and N divides the model-axis size; None means
    take the unsharded path (the same divisibility fallback resolve_spec
    applies to the weight tree, so compute and layout always agree).
    """
    sh = shard_info()
    if sh is None or backend == "dense":
        return None
    mesh, axis = sh
    if n % dict(mesh.shape)[axis]:
        return None
    return sh


# ---------------------------------------------------------------------------
# Tile selection + autotune table
# ---------------------------------------------------------------------------

# (method, M-bucket, N, K, codebook, dtype-name, block_size) -> (bm, bn, bk)
_AUTOTUNE: dict[tuple, tuple[int, int, int]] = {}


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _spec_of(codebook_name: str):
    from repro.core.quantize import pack_spec

    return pack_spec(codebook_name)


def _m_bucket(m: int) -> int:
    """Power-of-two token bucket so decode (M=1..8) and prefill share keys."""
    return 1 << max(3, (max(m, 1) - 1).bit_length())


def autotune_key(method: str, m: int, n: int, k: int, codebook: str,
                 dtype, block_size: int | None = None) -> tuple:
    # block_size is part of the key: same-(N, K) layers with different
    # effective block sizes need bk-compatible tilings (bk % bs or bs % bk)
    return (method, _m_bucket(m), n, k, codebook, jnp.dtype(dtype).name,
            block_size)


def lookup_tiles(method, m, n, k, codebook, dtype, block_size=None):
    return _AUTOTUNE.get(
        autotune_key(method, m, n, k, codebook, dtype, block_size))


def register_tiles(method, m, n, k, codebook, dtype,
                   tiles: tuple[int, int, int],
                   block_size: int | None = None) -> None:
    key = autotune_key(method, m, n, k, codebook, dtype, block_size)
    _AUTOTUNE[key] = tuple(tiles)


def autotune_table() -> dict:
    """Read-only snapshot of the autotune table (for benchmarks/reports)."""
    return dict(_AUTOTUNE)


# ---------------------------------------------------------------------------
# Autotune persistence (REPRO_AUTOTUNE_CACHE=<json path>)
# ---------------------------------------------------------------------------

_AUTOTUNE_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"


def _autotune_cache_path(path: str | None = None) -> str | None:
    return path or os.environ.get(_AUTOTUNE_CACHE_ENV) or None


def save_autotune_table(path: str | None = None) -> str | None:
    """Write the in-process table to JSON (``path`` or the env default).

    Returns the path written, or None when no destination is configured —
    callers can treat persistence as strictly optional.
    """
    path = _autotune_cache_path(path)
    if not path:
        return None
    # merge-then-write narrows (not closes) the lost-update window between
    # concurrent shards sharing one cache file: a shard that replaces the
    # file between this load and our rename below still loses its entries.
    # Best-effort is fine for a tuning cache — a dropped entry only costs
    # a re-autotune; correctness never depends on the file.
    load_autotune_table(path)
    entries = [{"key": list(k), "tiles": list(v)}
               for k, v in sorted(_AUTOTUNE.items(), key=str)]
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"version": 1, "entries": entries}, f, indent=1)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)  # atomic rename: readers never see a torn file
    return path


def load_autotune_table(path: str | None = None, *,
                        overwrite: bool = False) -> int:
    """Merge a persisted table into the process (in-process entries win
    unless ``overwrite``).  Missing/corrupt files are *tolerated* — a stale
    or bit-rotted cache must never break serving — but corruption is
    surfaced with a warning so operators know tiles fell back to the
    heuristic.  Returns the number of entries merged.
    """
    path = _autotune_cache_path(path)
    if not path or not os.path.exists(path):
        return 0
    try:
        with open(path) as f:
            data = json.load(f)
        entries = data["entries"]
    except (OSError, ValueError, KeyError, TypeError) as e:
        warnings.warn(
            f"autotune cache {path!r} is unreadable ({e!r}); ignoring it — "
            "kernels fall back to heuristic tiles until re-autotuned",
            RuntimeWarning, stacklevel=2)
        return 0
    n, bad = 0, 0
    for e in entries:
        try:
            key = tuple(e["key"])
            tiles = tuple(int(t) for t in e["tiles"])
        except (KeyError, TypeError, ValueError):
            bad += 1
            continue
        if len(tiles) != 3:
            bad += 1
            continue
        if overwrite or key not in _AUTOTUNE:
            _AUTOTUNE[key] = tiles
            n += 1
    if bad:
        warnings.warn(
            f"autotune cache {path!r}: skipped {bad} malformed "
            f"entr{'y' if bad == 1 else 'ies'} (kept {n})",
            RuntimeWarning, stacklevel=2)
    return n


load_autotune_table()  # import-time: benchmark-found tiles from prior runs


def _dividing_tile(extent: int, unit: int, cap: int) -> int:
    """The largest multiple of ``unit`` up to ``cap`` that divides
    ``extent``, so the operand needs no padding; else ``cap`` shrunk to the
    extent rounded up to ``unit``."""
    if extent % unit == 0:
        for t in range(cap // unit * unit, 0, -unit):
            if extent % t == 0:
                return t
    return min(cap, _round_up(extent, unit))


def tile_for(method: str, m: int, n: int, k: int, codebook: str, dtype,
             block_size: int | None = None) -> tuple[int, int, int]:
    """Tile choice: autotune-table hit, else a lane-aligned heuristic.

    Defaults follow the kernel docstrings (bm 128 / bn 256 / bk 512), shrunk
    to the (padded) problem: bm to a sublane multiple, bn/bk to lane
    multiples, bk additionally to a pack multiple and — for blockwise — to a
    block_size-compatible value (the per-plane width bk/g nests with bs).
    bn and bk prefer a smaller tile that divides N / K over padding: padding
    K re-packs the whole code matrix on every call (:func:`_pad_codes`).
    """
    hit = lookup_tiles(method, m, n, k, codebook, dtype, block_size)
    if hit is not None:
        return hit
    ps = _spec_of(codebook)
    bm = min(128, _round_up(m, 8))
    bn = _dividing_tile(n, 128, 256)
    if ps.group_bytes == 1:
        # historical unit: bk a multiple of 128·codes-per-byte so the packed
        # q tile width stays lane-aligned
        unit = 128 * ps.group_codes
        bk = _dividing_tile(k, unit, max(512, unit))
    else:
        # cross-byte groups (3-bit): prefer the smallest bk whose packed
        # width is lane-aligned (1024 → 384 bytes = 3 lanes); for small K
        # fall back to lane-aligned *logical* tiles with whole pack groups
        # rather than padding K up to 1024
        unit = ps.group_codes * (128 // math.gcd(ps.group_bytes, 128))
        bk = min(max(512, unit),
                 _round_up(k, math.lcm(ps.group_codes, 128)))
    if block_size is not None:
        # each kernel step covers t = bk/g columns of one code plane; t and
        # the block size must nest (t % bs == 0 or bs % t == 0)
        t = bk // ps.group_codes
        if t >= block_size:
            t = (t // block_size) * block_size
        elif block_size % t:
            t = math.gcd(t, block_size)
        bk = t * ps.group_codes
    return bm, bn, bk


def _pad2(arr, rows, cols):
    pr, pc = rows - arr.shape[0], cols - arr.shape[1]
    if pr == 0 and pc == 0:
        return arr
    return jnp.pad(arr, ((0, pr), (0, pc)))


def _pad_codes(q_packed, rows, kp, codebook):
    """Packed codes padded to ``rows`` and a logical K of ``kp``.  Rows pad
    with zero bytes; K re-packs (a plane is K/group wide, so a wider row's
    planes are not a byte-prefix of the narrow one's), which unpacks and
    packs the whole matrix.  :func:`tile_for` picks a bk dividing K wherever
    a lane-aligned one exists, so only K that is not a multiple of
    128·group codes (e.g. 896 at nf4) takes this path."""
    q_packed = repack_width(q_packed, kp, codebook)
    return _pad2(q_packed, rows, q_packed.shape[1])


# ---------------------------------------------------------------------------
# Fused lords forward (frozen / peft): y = x @ (lut[Q] ⊙ (B·A))ᵀ
# ---------------------------------------------------------------------------


def _lords_forward(x2d, q_packed, b, a, codebook, backend, tiles):
    if backend == "ref":
        return ref.lords_matmul_ref(x2d, q_packed, b, a, codebook)
    m, k = x2d.shape
    n = q_packed.shape[0]
    bm, bn, bk = tiles or tile_for("lords", m, n, k, codebook, x2d.dtype)
    interp = backend == "interpret"
    if m <= DECODE_M_MAX:
        # decode fast path: weight-stationary GEMV kernel, M padded to the
        # sublane tile inside the kernel (bm from the tile table is moot)
        np_, kp = _round_up(n, bn), _round_up(k, bk)
        y = lords_decode_pallas(
            _pad2(x2d, m, kp),
            _pad_codes(q_packed, np_, kp, codebook),
            _pad2(b, np_, b.shape[1]),
            _pad2(a, a.shape[0], kp),
            codebook,
            bn=bn, bk=bk,
            interpret=interp,
        )
        return y[:, :n]
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    y = lords_matmul_pallas(
        _pad2(x2d, mp, kp),
        _pad_codes(q_packed, np_, kp, codebook),
        _pad2(b, np_, b.shape[1]),
        _pad2(a, a.shape[0], kp),
        codebook,
        bm=bm, bn=bn, bk=bk,
        interpret=interp,
    )
    return y[:m, :n]


def _lords_grads(g, x2d, q_packed, b, a, w, codebook, backend):
    """Fused backward family: dx = g·Ŵ via the transposed kernel, rank-space
    dB/dA (and the QAT dW/∂S STE terms) via the tiled grad-reduction kernel
    — no (N, K) f32 dequantized temporary on fused backends.  Returns
    ``(dx, db, da)`` in f32 (+ ``dw`` when the qat master ``w`` is given).
    """
    if backend not in _FUSED:
        return ref.lords_grads_ref(g, x2d, q_packed, b, a, codebook, w=w)
    m, k = x2d.shape
    n = q_packed.shape[0]
    # the `transposed` autotune key: one tile triple drives both bwd kernels
    bm, bn, bk = tile_for("lords_t", m, n, k, codebook, jnp.float32)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    interp = backend == "interpret"
    g32 = _pad2(g.astype(jnp.float32), mp, np_)
    x32 = _pad2(x2d.astype(jnp.float32), mp, kp)
    qp = _pad_codes(q_packed, np_, kp, codebook)
    bp = _pad2(b.astype(jnp.float32), np_, b.shape[1])
    ap = _pad2(a.astype(jnp.float32), a.shape[0], kp)
    dx = lords_matmul_t_pallas(
        g32, qp, bp, ap, codebook, bm=bm, bn=bn, bk=bk, interpret=interp,
    )[:m, :k]
    wp = None if w is None else _pad2(w.astype(jnp.float32), np_, kp)
    out = lords_grad_pallas(
        x32, g32, qp, bp, ap, codebook, w=wp,
        bm=bm, bn=bn, bk=bk, interpret=interp,
    )
    db = out[0][:, :n].T                       # dbT (r, Np) -> dB (N, r)
    da = out[1].sum(axis=0)[:, :k]             # Σ_j da_part -> dA (r, K)
    if w is None:
        return dx, db, da
    return dx, db, da, out[2][:n, :k]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _lords_qmatmul(x2d, q_packed, b, a, codebook, backend, tiles):
    return _lords_forward(x2d, q_packed, b, a, codebook, backend, tiles)


def _lords_fwd(x2d, q_packed, b, a, codebook, backend, tiles):
    y = _lords_forward(x2d, q_packed, b, a, codebook, backend, tiles)
    return y, (x2d, q_packed, b, a)


def _lords_bwd(codebook, backend, tiles, res, g):
    x2d, q_packed, b, a = res
    dx, db, da = _lords_grads(g, x2d, q_packed, b, a, None, codebook, backend)
    dq = np.zeros(q_packed.shape, jax.dtypes.float0)   # int codes: no grad
    return (dx.astype(x2d.dtype), dq, db.astype(b.dtype), da.astype(a.dtype))


_lords_qmatmul.defvjp(_lords_fwd, _lords_bwd)


# ---------------------------------------------------------------------------
# Fused lords QAT: y = x @ (ROUND(W ⊘ BA) ⊙ BA)ᵀ with STE cotangents
# ---------------------------------------------------------------------------


def _lords_qat_forward(x2d, w, b, a, codebook, backend, tiles):
    """Returns (y, q_packed).  Fused backends run the lut_quantize kernel and
    feed its packed codes straight into the fused matmul — Ŵ never exists."""
    if backend == "ref":
        q_packed = ref.lut_quantize_ref(w, b, a, codebook)
        return ref.lords_matmul_ref(x2d, q_packed, b, a, codebook), q_packed
    m, k = x2d.shape
    n = w.shape[0]
    ps = _spec_of(codebook)
    bm, bn, bk = tiles or tile_for("lords", m, n, k, codebook, x2d.dtype)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    interp = backend == "interpret"
    bp = _pad2(b, np_, b.shape[1])
    ap = _pad2(a, a.shape[0], kp)
    qp = lut_quantize_pallas(
        _pad2(w, np_, kp), bp, ap, codebook, bn=bn, bk=bk, interpret=interp
    )
    y = lords_matmul_pallas(
        _pad2(x2d, mp, kp), qp, bp, ap, codebook,
        bm=bm, bn=bn, bk=bk, interpret=interp,
    )
    # codes back at the logical K, rounded up to whole pack groups —
    # trailing codes past k (if any) decode under zero-padded activations
    return y[:m, :n], repack_width(qp[:n], _round_up(k, ps.group_codes),
                                   codebook)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _lords_qat_qmatmul(x2d, w, b, a, codebook, backend, tiles):
    y, _ = _lords_qat_forward(x2d, w, b, a, codebook, backend, tiles)
    return y


def _lords_qat_fwd(x2d, w, b, a, codebook, backend, tiles):
    y, q_packed = _lords_qat_forward(x2d, w, b, a, codebook, backend, tiles)
    return y, (x2d, w, b, a, q_packed)


def _lords_qat_bwd(codebook, backend, tiles, res, g):
    # the packed codes saved by the forward feed the backward kernels
    # directly — no second quantization or dequantization pass
    x2d, w, b, a, q_packed = res
    dx, db, da, dw = _lords_grads(g, x2d, q_packed, b, a, w, codebook,
                                  backend)
    return (dx.astype(x2d.dtype), dw.astype(w.dtype),
            db.astype(b.dtype), da.astype(a.dtype))


_lords_qat_qmatmul.defvjp(_lords_qat_fwd, _lords_qat_bwd)


# ---------------------------------------------------------------------------
# Fused block-wise baseline: y = x @ (lut[Q] ⊙ repeat(s_blk))ᵀ
# ---------------------------------------------------------------------------


def _block_padded(q_packed, s_blk, m, n, k, block_size, bm, bn, bk,
                  codebook):
    """Shared fwd/bwd block-operand padding: K rounds to lcm(bk, block_size)
    so tiles and blocks stay commensurate, padded scales are 1.0 (never the
    eps clamp), padded rows/cols contribute zeros.  One helper so the
    forward and its VJP can never pad differently."""
    kmult = bk * block_size // math.gcd(bk, block_size)  # lcm: tiles + blocks
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, kmult)
    qp = _pad_codes(q_packed, np_, kp, codebook)
    s_pad = jnp.pad(
        s_blk,
        ((0, np_ - n), (0, kp // block_size - s_blk.shape[1])),
        constant_values=1.0,
    )
    return qp, s_pad, mp, np_, kp


def _block_forward(x2d, q_packed, s_blk, block_size, codebook, backend, tiles):
    if backend == "ref":
        return ref.block_matmul_ref(x2d, q_packed, s_blk, block_size, codebook)
    m, k = x2d.shape
    n = q_packed.shape[0]
    bm, bn, bk = tiles or tile_for(
        "blockwise", m, n, k, codebook, x2d.dtype, block_size=block_size)
    qp, s_pad, mp, np_, kp = _block_padded(
        q_packed, s_blk, m, n, k, block_size, bm, bn, bk, codebook)
    y = block_matmul_pallas(
        _pad2(x2d, mp, kp),
        qp,
        s_pad,
        block_size,
        codebook,
        bm=bm, bn=bn, bk=bk,
        interpret=(backend == "interpret"),
    )
    return y[:m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _block_qmatmul(x2d, q_packed, s_blk, block_size, codebook, backend, tiles):
    return _block_forward(x2d, q_packed, s_blk, block_size, codebook, backend,
                          tiles)


def _block_fwd(x2d, q_packed, s_blk, block_size, codebook, backend, tiles):
    y = _block_forward(x2d, q_packed, s_blk, block_size, codebook, backend,
                       tiles)
    return y, (x2d, q_packed, s_blk)


def _block_grads(g, x2d, q_packed, s_blk, block_size, codebook, backend):
    """Fused block-wise backward: transposed dequant-matmul for dx + tiled
    per-block ∂s reduction — the blockwise mirror of :func:`_lords_grads`."""
    if backend not in _FUSED:
        return ref.block_grads_ref(g, x2d, q_packed, s_blk, block_size,
                                   codebook)
    m, k = x2d.shape
    n = q_packed.shape[0]
    bm, bn, bk = tile_for("blockwise_t", m, n, k, codebook, jnp.float32,
                          block_size=block_size)
    qp, s_pad, mp, np_, kp = _block_padded(
        q_packed, s_blk.astype(jnp.float32), m, n, k, block_size,
        bm, bn, bk, codebook)
    interp = backend == "interpret"
    g32 = _pad2(g.astype(jnp.float32), mp, np_)
    x32 = _pad2(x2d.astype(jnp.float32), mp, kp)
    dx = block_matmul_t_pallas(
        g32, qp, s_pad, block_size, codebook,
        bm=bm, bn=bn, bk=bk, interpret=interp,
    )[:m, :k]
    ds_blk = block_grad_pallas(
        x32, g32, qp, block_size, codebook,
        bm=bm, bn=bn, bk=bk, interpret=interp,
    )[:n, : s_blk.shape[1]]
    return dx, ds_blk


def _block_bwd(block_size, codebook, backend, tiles, res, g):
    x2d, q_packed, s_blk = res
    dx, ds_blk = _block_grads(g, x2d, q_packed, s_blk, block_size, codebook,
                              backend)
    dq = np.zeros(q_packed.shape, jax.dtypes.float0)
    return dx.astype(x2d.dtype), dq, ds_blk.astype(s_blk.dtype)


_block_qmatmul.defvjp(_block_fwd, _block_bwd)


# ---------------------------------------------------------------------------
# Sharded fused paths: shard_map over the mesh, psum'd cotangents
# ---------------------------------------------------------------------------
#
# Layout per (N, K) linear with model parallelism p and data parallelism d
# (the product of the remaining mesh axes, used when the flattened token
# count divides it):
#   codes Q (N/p, K/pack) · B (N/p, r) · W (N/p, K) · s_blk (N/p, K/bs)
#   row-shard over 'model'; A (r, K) replicates; x (M/d, K) shards its
#   token dim over the data axes; y comes out (M/d, N/p).  Each device
#   runs the *same* fused kernel bodies as the unsharded path on its
#   (token-slice × row-slice) block — Ŵ never exists anywhere.
# Backward psums follow from the layout: dx is token-local but partial
#   over the row shards (psum 'model'); dB / dW / ds_blk are row-local but
#   partial over the token shards (psum data axes); dA is partial over
#   both (psum all).  When M doesn't divide d, x replicates and the
#   data-axis psums drop out.
# The custom VJPs sit *outside* shard_map (explicit psums instead of
# relying on transpose-of-manual replication rules, which custom_vjp
# bodies cannot declare).


def _dp_axes(mesh, axis, m_tokens: int) -> tuple:
    """Mesh axes the token dim shards over: every non-model axis, kept only
    when the flattened token count divides their product (else replicate,
    matching resolve_spec's divisibility behavior for activations)."""
    shape = dict(mesh.shape)
    axes = tuple(a for a, size in shape.items() if a != axis and size > 1)
    size = 1
    for a in axes:
        size *= shape[a]
    if not axes or m_tokens % size:
        return ()
    return axes


def _psum(v, axes):
    return jax.lax.psum(v, axes) if axes else v


def _tp_specs(axis, batch: tuple):
    bspec = batch if len(batch) > 1 else (batch[0] if batch else None)
    xs = PartitionSpec(bspec, None)     # x / dx: tokens over the data axes
    row = PartitionSpec(axis, None)     # codes / B / W / s_blk rows
    rep = PartitionSpec()               # A
    out = PartitionSpec(bspec, axis)    # y / g
    return xs, row, rep, out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _shlords_qmatmul(x2d, q_packed, b, a, codebook, backend, mesh, axis,
                     tiles):
    xs, row, rep, out = _tp_specs(axis, _dp_axes(mesh, axis, x2d.shape[0]))
    return jax.shard_map(
        lambda xl, ql, bl, al: _lords_forward(
            xl, ql, bl, al, codebook, backend, tiles),
        mesh=mesh, in_specs=(xs, row, row, rep), out_specs=out,
        check_vma=False,
    )(x2d, q_packed, b, a)


def _shlords_fwd(x2d, q_packed, b, a, codebook, backend, mesh, axis, tiles):
    y = _shlords_qmatmul(x2d, q_packed, b, a, codebook, backend, mesh, axis,
                         tiles)
    return y, (x2d, q_packed, b, a)


def _shlords_bwd(codebook, backend, mesh, axis, tiles, res, g):
    x2d, q_packed, b, a = res
    dp = _dp_axes(mesh, axis, x2d.shape[0])
    xs, row, rep, out = _tp_specs(axis, dp)

    def body(gl, xl, ql, bl, al):
        dx, db, da = _lords_grads(gl, xl, ql, bl, al, None, codebook, backend)
        return jax.lax.psum(dx, axis), _psum(db, dp), _psum(da, dp + (axis,))

    dx, db, da = jax.shard_map(
        body, mesh=mesh, in_specs=(out, xs, row, row, rep),
        out_specs=(xs, row, rep), check_vma=False,
    )(g, x2d, q_packed, b, a)
    dq = np.zeros(q_packed.shape, jax.dtypes.float0)
    return (dx.astype(x2d.dtype), dq, db.astype(b.dtype), da.astype(a.dtype))


_shlords_qmatmul.defvjp(_shlords_fwd, _shlords_bwd)


def _shlords_qat_forward(x2d, w, b, a, codebook, backend, mesh, axis, tiles):
    """Shared primal/fwd body: returns (y, row-sharded packed codes)."""
    xs, row, rep, out = _tp_specs(axis, _dp_axes(mesh, axis, x2d.shape[0]))
    return jax.shard_map(
        lambda xl, wl, bl, al: _lords_qat_forward(
            xl, wl, bl, al, codebook, backend, tiles),
        mesh=mesh, in_specs=(xs, row, row, rep), out_specs=(out, row),
        check_vma=False,
    )(x2d, w, b, a)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _shlords_qat_qmatmul(x2d, w, b, a, codebook, backend, mesh, axis, tiles):
    y, _ = _shlords_qat_forward(x2d, w, b, a, codebook, backend, mesh, axis,
                                tiles)
    return y


def _shlords_qat_fwd(x2d, w, b, a, codebook, backend, mesh, axis, tiles):
    y, q_packed = _shlords_qat_forward(x2d, w, b, a, codebook, backend, mesh,
                                       axis, tiles)
    # the row-sharded packed codes ride to the backward exactly as saved —
    # each shard re-reads its own codes, no re-quantization pass
    return y, (x2d, w, b, a, q_packed)


def _shlords_qat_bwd(codebook, backend, mesh, axis, tiles, res, g):
    x2d, w, b, a, q_packed = res
    dp = _dp_axes(mesh, axis, x2d.shape[0])
    xs, row, rep, out = _tp_specs(axis, dp)

    def body(gl, xl, ql, bl, al, wl):
        dx, db, da, dw = _lords_grads(gl, xl, ql, bl, al, wl, codebook,
                                      backend)
        return (jax.lax.psum(dx, axis), _psum(db, dp),
                _psum(da, dp + (axis,)), _psum(dw, dp))

    dx, db, da, dw = jax.shard_map(
        body, mesh=mesh, in_specs=(out, xs, row, row, rep, row),
        out_specs=(xs, row, rep, row), check_vma=False,
    )(g, x2d, q_packed, b, a, w)
    return (dx.astype(x2d.dtype), dw.astype(w.dtype),
            db.astype(b.dtype), da.astype(a.dtype))


_shlords_qat_qmatmul.defvjp(_shlords_qat_fwd, _shlords_qat_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _shblock_qmatmul(x2d, q_packed, s_blk, block_size, codebook, backend,
                     mesh, axis, tiles):
    xs, row, rep, out = _tp_specs(axis, _dp_axes(mesh, axis, x2d.shape[0]))
    return jax.shard_map(
        lambda xl, ql, sl: _block_forward(
            xl, ql, sl, block_size, codebook, backend, tiles),
        mesh=mesh, in_specs=(xs, row, row), out_specs=out,
        check_vma=False,
    )(x2d, q_packed, s_blk)


def _shblock_fwd(x2d, q_packed, s_blk, block_size, codebook, backend, mesh,
                 axis, tiles):
    y = _shblock_qmatmul(x2d, q_packed, s_blk, block_size, codebook, backend,
                         mesh, axis, tiles)
    return y, (x2d, q_packed, s_blk)


def _shblock_bwd(block_size, codebook, backend, mesh, axis, tiles, res, g):
    x2d, q_packed, s_blk = res
    dp = _dp_axes(mesh, axis, x2d.shape[0])
    xs, row, rep, out = _tp_specs(axis, dp)

    def body(gl, xl, ql, sl):
        dx, ds = _block_grads(gl, xl, ql, sl, block_size, codebook, backend)
        return jax.lax.psum(dx, axis), _psum(ds, dp)

    dx, ds_blk = jax.shard_map(
        body, mesh=mesh, in_specs=(out, xs, row, row),
        out_specs=(xs, row), check_vma=False,
    )(g, x2d, q_packed, s_blk)
    dq = np.zeros(q_packed.shape, jax.dtypes.float0)
    return dx.astype(x2d.dtype), dq, ds_blk.astype(s_blk.dtype)


_shblock_qmatmul.defvjp(_shblock_fwd, _shblock_bwd)


# ---------------------------------------------------------------------------
# Dense fallback — the legacy materialize-Ŵ path
# ---------------------------------------------------------------------------


def _dense_base(params, x2d, spec, n, m):
    from repro.core.lords import dequantize_weight

    w_hat = dequantize_weight(params, spec, n, m)
    return jnp.einsum("tk,nk->tn", x2d.astype(spec.compute_dtype), w_hat)


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------


def _fused_supported(params: dict, spec) -> bool:
    method, mode = spec.method, spec.mode
    if "awq_s" in params:  # per-channel smoothing must be un-folded densely
        return False
    if method == "lords":
        return True
    if method == "blockwise":
        return mode != "qat"  # blockwise QAT trains s_blk through STE: dense
    if method in ("qlora", "loftq", "qpissa"):
        return True  # frozen block-quantized base + additive adapter
    return False


def _block_operands(params: dict, m: int):
    from repro.core.baselines import baseline_block_operands

    return baseline_block_operands(params, m)


def qmatmul(params: dict, x: jnp.ndarray, spec, n: int, m: int, *,
            backend: str | None = None,
            tiles: tuple[int, int, int] | None = None) -> jnp.ndarray:
    """y = x @ Ŵᵀ (+ additive adapter + bias) for any QuantSpec.

    ``x`` may carry arbitrary leading batch dims over the in-features axis
    ``m``; the result replaces that axis with ``n``.  Backend selection,
    padding, and differentiability are described in the module docstring.
    Its ops, the kernel launch and the glue around it, run in the named
    scope ``qmatmul``.
    """
    with jax.named_scope("qmatmul"):
        return _qmatmul(params, x, spec, n, m, backend, tiles)


def _qmatmul(params, x, spec, n, m, backend, tiles):
    backend = _resolve(backend)
    method, mode = spec.method, spec.mode
    cd = spec.compute_dtype
    lead = x.shape[:-1]
    x2d = x.reshape(-1, m)

    if backend == "dense" or not _fused_supported(params, spec):
        # also the 'none' method: a plain einsum on the unquantized weight
        # (GSPMD partitions it directly — no shard_map route needed)
        y2d = _dense_base(params, x2d, spec, n, m)
    elif method == "lords":
        xc = x2d.astype(cd)
        b = params["b"].astype(spec.ba_compute_dtype)
        a = params["a"].astype(spec.ba_compute_dtype)
        tp = _tp_shard(backend, n)
        if mode == "qat":
            if tp is not None:
                y2d = _shlords_qat_qmatmul(
                    xc, params["w"], b, a, spec.codebook, backend, *tp,
                    tiles)
            else:
                y2d = _lords_qat_qmatmul(
                    xc, params["w"], b, a, spec.codebook, backend, tiles)
        else:
            if tp is not None:
                y2d = _shlords_qmatmul(
                    xc, params["q"], b, a, spec.codebook, backend, *tp,
                    tiles)
            else:
                y2d = _lords_qmatmul(
                    xc, params["q"], b, a, spec.codebook, backend, tiles)
        y2d = y2d.astype(cd)
    else:  # blockwise base (also the qlora/loftq/qpissa frozen base)
        q_packed, s_blk, bs = _block_operands(params, m)
        tp = _tp_shard(backend, n)
        if tp is not None:
            y2d = _shblock_qmatmul(
                x2d.astype(cd), q_packed, s_blk, bs, spec.codebook,
                backend, *tp, tiles)
        else:
            y2d = _block_qmatmul(
                x2d.astype(cd), q_packed, s_blk, bs, spec.codebook,
                backend, tiles)
        y2d = y2d.astype(cd)

    if method in ("qlora", "loftq", "qpissa") and "lora_a" in params:
        # unmergeable additive adapter: y += x @ Aᵀ Bᵀ (the extra GEMM the
        # paper's Fig. 2 measures against LoRDS)
        xa = jnp.einsum("tk,rk->tr", x2d.astype(cd),
                        params["lora_a"].astype(cd))
        y2d = y2d + jnp.einsum("tr,nr->tn", xa, params["lora_b"].astype(cd))
    if "bias" in params:
        y2d = y2d + params["bias"].astype(y2d.dtype)
    return y2d.reshape(*lead, n)


# ---------------------------------------------------------------------------
# Fused attention dispatch (flash prefill + quantized-KV decode)
# ---------------------------------------------------------------------------
#
# ``qattention(kind, ...)`` is the attention analogue of :func:`qmatmul`:
# one entry point per hot attention shape, with the same backend precedence
# (explicit > backend_scope > env > platform), pad-to-tile, shard_scope
# integration, and autotuned tiles persisted through REPRO_AUTOTUNE_CACHE.
#
#   kind="prefill"     flash-style causal prefill (attn_prefill_pallas):
#                      q (b,s,nh,hd) · k/v (b,s,nkv,hd) unexpanded-GQA,
#                      ragged `positions` (b,s) mask, never materializes
#                      the (chunk, S) score matrix.  Differentiable: the
#                      custom VJP recomputes through the ref oracle (same
#                      peak memory as the rematerialized einsum path QAT /
#                      PEFT training already pays).
#   kind="decode"      fused GQA decode (attn_decode_gqa_pallas): the int8
#                      cache streams once at int8 width, per-(token, head)
#                      scales fold into the score/output dots in VMEM.
#   kind="mla_decode"  fused absorbed-latent MLA decode
#                      (attn_decode_mla_pallas): int8 latent + per-token
#                      scale, output is the weighted latent.
#   kind="chunk_prefill"
#                      the prefill kernel with *separate* q / key positions
#                      (q length != key length): chunk queries against the
#                      gathered prefix window + the raw in-flight chunk —
#                      the chunked-prefill step of the continuous-batching
#                      engine.  Serving-only: no VJP.
#   kind="paged_decode" / "paged_mla_decode"
#                      block-paged variants of the decode kinds: the KV
#                      lives in a global page pool (P, ps, ...) read through
#                      a per-sequence page table (b, np), scalar-prefetched
#                      — the int8 pool is read as stored, no gather into a
#                      contiguous temp (the ref oracles *do* gather; that
#                      gather is the jaxpr-guard negative control).  Paged
#                      GQA copies only each slot's live pages (pos + 1
#                      tokens), all KV heads in one grid step; paged MLA
#                      still walks every page of the window.
#
# Sharding: attention is head-local and batch-local, so inside a
# shard_scope the fused kernels run under shard_map with heads on the
# 'model' axis and the batch on the data axes — psum-free in both
# directions.  Head counts that don't divide the model axis fall back to
# the unsharded call (GSPMD handles the ref path directly).

_ATTN_CODEBOOK = "attn"     # codebook slot of attention autotune keys
_ATTN_KINDS = ("prefill", "chunk_prefill", "decode", "mla_decode",
               "paged_decode", "paged_mla_decode")
_ATTN_METHOD = {"prefill": "attn_prefill", "chunk_prefill": "attn_chunk",
                "decode": "attn_gqa", "mla_decode": "attn_mla",
                "paged_decode": "attn_gqa_paged",
                "paged_mla_decode": "attn_mla_paged"}


def attn_tile_for(kind: str, seq: int, heads: int, depth: int, kv_dtype,
                  default: tuple[int, int]) -> tuple[int, int]:
    """(row-tile, kv-tile) for an attention launch: autotune-table hit under
    the shared key machinery (method ``attn_*``, codebook ``"attn"``, dtype
    = the *cache* dtype so int8 and bf16 caches tune independently), else
    ``default``.  Triples in the table carry a trailing 1 (the bk slot is
    meaningless for attention)."""
    hit = lookup_tiles(_ATTN_METHOD[kind], seq, heads, depth,
                       _ATTN_CODEBOOK, kv_dtype)
    if hit is not None:
        return hit[0], hit[1]
    return default


def _attn_shard(backend: str, nh: int, nkv: int) -> tuple | None:
    """Shard route for a head-local attention call: active scope + fused
    backend + both head counts divide the model axis."""
    sh = shard_info()
    if sh is None or backend not in _FUSED:
        return None
    mesh, axis = sh
    tp = dict(mesh.shape)[axis]
    if nh % tp or nkv % tp:
        return None
    return sh


def _decode_kmask(pos, cap: int):
    """(b, S) additive liveness mask: 0 where the cache slot is live
    (index <= pos, covering padded slots too since pos < S), NEG_INF else."""
    live = jnp.arange(cap, dtype=jnp.int32)[None, :] <= pos[:, None]
    return jnp.where(live, 0.0, ref.ATTN_NEG_INF).astype(jnp.float32)


def _pad_axis(arr, axis: int, to: int, value=0):
    pad = to - arr.shape[axis]
    if pad == 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths, constant_values=value)


# ---- prefill ----


def _attn_prefill_run(q, k, v, positions, logit_scale, backend, tiles):
    """Pad-to-tile + flash kernel, all in the model's native layouts.
    q (b,s,nh,hd), k/v (b,s,nkv,hd), positions (b,s) → (b,s,nh,hdv) f32."""
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    bq, bkv = tiles or attn_tile_for(
        "prefill", s, nh, hd, k.dtype, (128, 128))
    bq, bkv = min(bq, _round_up(s, 8)), min(bkv, _round_up(s, 8))
    sq, skv = _round_up(s, bq), _round_up(s, bkv)
    qt = _pad_axis(q, 1, sq)
    kt = _pad_axis(k, 1, skv)
    vt = _pad_axis(v, 1, skv)
    qpos = _pad_axis(positions, 1, sq, value=-1)
    kpos = _pad_axis(positions, 1, skv, value=-1)
    y = attn_prefill_pallas(
        qt, kt, vt, qpos, kpos, logit_scale=float(logit_scale),
        bq=bq, bkv=bkv, interpret=(backend == "interpret"))
    return y[:, :s]


def _attn_prefill_fused(q, k, v, positions, logit_scale, backend, tiles):
    tp = _attn_shard(backend, q.shape[2], k.shape[2])
    if tp is None:
        return _attn_prefill_run(q, k, v, positions, logit_scale, backend,
                                 tiles)
    mesh, axis = tp
    dp = _dp_axes(mesh, axis, q.shape[0])
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    hspec = PartitionSpec(bspec, None, axis, None)
    pspec = PartitionSpec(bspec, None)
    return jax.shard_map(
        lambda ql, kl, vl, pl_: _attn_prefill_run(
            ql, kl, vl, pl_, logit_scale, backend, tiles),
        mesh=mesh, in_specs=(hspec, hspec, hspec, pspec), out_specs=hspec,
        check_vma=False,
    )(q, k, v, positions)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attn_prefill_qdisp(q, k, v, positions, logit_scale, backend, tiles):
    return _attn_prefill_fused(q, k, v, positions, logit_scale, backend,
                               tiles)


def _attn_prefill_fwd(q, k, v, positions, logit_scale, backend, tiles):
    y = _attn_prefill_fused(q, k, v, positions, logit_scale, backend, tiles)
    return y, (q, k, v, positions)


def _attn_prefill_bwd(logit_scale, backend, tiles, res, g):
    # backward recomputes through the materializing oracle — attention
    # training cost matches the rematerialized einsum path; the fused
    # kernel is the *serving* fast path (decode never differentiates)
    q, k, v, positions = res
    _, vjp = jax.vjp(
        lambda qq, kk, vv: ref.attn_prefill_ref(qq, kk, vv, positions,
                                                float(logit_scale)),
        q, k, v)
    dq, dk, dv = vjp(g.astype(jnp.float32))
    dpos = np.zeros(positions.shape, jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dpos)


_attn_prefill_qdisp.defvjp(_attn_prefill_fwd, _attn_prefill_bwd)


# ---- chunked prefill (q length != key length) ----


def _attn_chunk_run(q, k, v, qpos, kpos, logit_scale, backend, tiles):
    """q (b,s,nh,hd) at qpos (b,s) vs k/v (b,S,nkv,hd) at kpos (b,S) →
    (b,s,nh,hdv) f32.  Same kernel as prefill — the flash kernel already
    takes separate query/key position arrays; only the padding differs
    (q and kv lengths round up to their tiles independently)."""
    b, s, nh, hd = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    bq, bkv = tiles or attn_tile_for(
        "chunk_prefill", skv, nh, hd, k.dtype, (128, 128))
    bq = min(bq, _round_up(s, 8))
    bkv = min(bkv, _round_up(skv, 8))
    sq, sk = _round_up(s, bq), _round_up(skv, bkv)
    qt = _pad_axis(q, 1, sq)
    kt = _pad_axis(k, 1, sk)
    vt = _pad_axis(v, 1, sk)
    qp = _pad_axis(qpos, 1, sq, value=-1)
    kp = _pad_axis(kpos, 1, sk, value=-1)
    y = attn_prefill_pallas(
        qt, kt, vt, qp, kp, logit_scale=float(logit_scale),
        bq=bq, bkv=bkv, interpret=(backend == "interpret"))
    return y[:, :s]


def _attn_chunk_fused(q, k, v, qpos, kpos, logit_scale, backend, tiles):
    tp = _attn_shard(backend, q.shape[2], k.shape[2])
    if tp is None:
        return _attn_chunk_run(q, k, v, qpos, kpos, logit_scale, backend,
                               tiles)
    mesh, axis = tp
    dp = _dp_axes(mesh, axis, q.shape[0])
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    hspec = PartitionSpec(bspec, None, axis, None)
    pspec = PartitionSpec(bspec, None)
    return jax.shard_map(
        lambda ql, kl, vl, qpl, kpl: _attn_chunk_run(
            ql, kl, vl, qpl, kpl, logit_scale, backend, tiles),
        mesh=mesh, in_specs=(hspec, hspec, hspec, pspec, pspec),
        out_specs=hspec, check_vma=False,
    )(q, k, v, qpos, kpos)


# ---- GQA decode ----


def _attn_decode_run(q, k, v, pos, k_scale, v_scale, logit_scale, backend,
                     tiles):
    """q (b,nh,hd) vs cache (b,S,nkv,hd) [+ scales (b,S,nkv)] →
    (b,nh,hdv) f32.  The cache operands go to the kernel in their stored
    layout (the index maps slice per-head tiles) — a transpose here would
    make XLA copy the whole cache every decode step."""
    b, nh, hd = q.shape
    cap, nkv = k.shape[1], k.shape[2]
    g = nh // nkv
    _, bs = tiles or attn_tile_for(
        "decode", cap, nh, hd, k.dtype, (DECODE_ROWS, 128))
    bs = min(bs, _round_up(cap, 8))
    capp = _round_up(cap, bs)
    g8 = _round_up(g, DECODE_ROWS)
    qg = _pad_axis(q.reshape(b, nkv, g, hd), 2, g8)
    kt = _pad_axis(k, 1, capp)
    vt = _pad_axis(v, 1, capp)
    kst = vst = None
    if k_scale is not None:
        kst = _pad_axis(k_scale, 1, capp)
        vst = _pad_axis(v_scale, 1, capp)
    y = attn_decode_gqa_pallas(
        qg, kt, vt, _decode_kmask(pos, capp), kst, vst,
        logit_scale=float(logit_scale), bs=bs,
        interpret=(backend == "interpret"))
    return y[:, :, :g].reshape(b, nh, v.shape[-1])


def _attn_decode_fused(q, k, v, pos, k_scale, v_scale, logit_scale, backend,
                       tiles):
    tp = _attn_shard(backend, q.shape[1], k.shape[2])
    if tp is None:
        return _attn_decode_run(q, k, v, pos, k_scale, v_scale, logit_scale,
                                backend, tiles)
    mesh, axis = tp
    dp = _dp_axes(mesh, axis, q.shape[0])
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    qspec = PartitionSpec(bspec, axis, None)
    cspec = PartitionSpec(bspec, None, axis, None)
    sspec = PartitionSpec(bspec, None, axis)
    pspec = PartitionSpec(bspec)

    def body(ql, kl, vl, posl, ksl, vsl):
        return _attn_decode_run(ql, kl, vl, posl, ksl, vsl, logit_scale,
                                backend, tiles)

    if k_scale is None:
        return jax.shard_map(
            lambda ql, kl, vl, posl: body(ql, kl, vl, posl, None, None),
            mesh=mesh, in_specs=(qspec, cspec, cspec, pspec),
            out_specs=qspec, check_vma=False,
        )(q, k, v, pos)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qspec, cspec, cspec, pspec, sspec, sspec),
        out_specs=qspec, check_vma=False,
    )(q, k, v, pos, k_scale, v_scale)


# ---- MLA decode ----


def _attn_mla_run(q_lat, q_rope, c, k_rope, pos, c_scale, logit_scale,
                  backend, tiles):
    """q_lat (b,nh,L) / q_rope (b,nh,R) vs c (b,S,L) + k_rope (b,S,R)
    [+ c_scale (b,S)] → weighted latent (b,nh,L) f32."""
    b, nh, lat = q_lat.shape
    cap = c.shape[1]
    _, bs = tiles or attn_tile_for(
        "mla_decode", cap, nh, lat, c.dtype, (DECODE_ROWS, 128))
    bs = min(bs, _round_up(cap, 8))
    capp = _round_up(cap, bs)
    nh8 = _round_up(nh, DECODE_ROWS)
    qlp = _pad_axis(q_lat, 1, nh8)
    qrp = _pad_axis(q_rope, 1, nh8)
    cp = _pad_axis(c, 1, capp)
    krp = _pad_axis(k_rope, 1, capp)
    csp = None if c_scale is None else _pad_axis(c_scale, 1, capp)
    y = attn_decode_mla_pallas(
        qlp, qrp, cp, krp, _decode_kmask(pos, capp), csp,
        logit_scale=float(logit_scale), bs=bs,
        interpret=(backend == "interpret"))
    return y[:, :nh]


def _attn_mla_fused(q_lat, q_rope, c, k_rope, pos, c_scale, logit_scale,
                    backend, tiles):
    tp = _attn_shard(backend, q_lat.shape[1], q_lat.shape[1])
    if tp is None:
        return _attn_mla_run(q_lat, q_rope, c, k_rope, pos, c_scale,
                             logit_scale, backend, tiles)
    mesh, axis = tp
    dp = _dp_axes(mesh, axis, q_lat.shape[0])
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    qspec = PartitionSpec(bspec, axis, None)    # heads shard
    cspec = PartitionSpec(bspec, None, None)    # latent cache replicates
    sspec = PartitionSpec(bspec, None)
    pspec = PartitionSpec(bspec)

    def body(qll, qrl, cl, krl, posl, csl):
        return _attn_mla_run(qll, qrl, cl, krl, posl, csl, logit_scale,
                             backend, tiles)

    if c_scale is None:
        return jax.shard_map(
            lambda qll, qrl, cl, krl, posl: body(qll, qrl, cl, krl, posl,
                                                 None),
            mesh=mesh, in_specs=(qspec, qspec, cspec, cspec, pspec),
            out_specs=qspec, check_vma=False,
        )(q_lat, q_rope, c, k_rope, pos)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qspec, qspec, cspec, cspec, pspec, sspec),
        out_specs=qspec, check_vma=False,
    )(q_lat, q_rope, c, k_rope, pos, c_scale)


# ---- paged GQA decode ----


def _attn_paged_run(q, k_pool, v_pool, pt, pos, k_scale, v_scale,
                    logit_scale, backend):
    """q (b,nh,hd) vs page pools (P,ps,nkv,hd) [+ scale pools (P,ps,nkv)]
    through the page table pt (b,np) → (b,nh,hdv) f32.  No padding of the
    pool and no gather: the kernel copies each slot's live pages, the
    ``pos + 1`` tokens of its context, straight from the pool."""
    b, nh, hd = q.shape
    nkv = k_pool.shape[2]
    g = nh // nkv
    g8 = _round_up(g, DECODE_ROWS)
    qg = _pad_axis(q.reshape(b, nkv, g, hd), 2, g8)
    y = attn_decode_gqa_paged_pallas(
        pt, qg, k_pool, v_pool, pos + 1, k_scale, v_scale,
        logit_scale=float(logit_scale), interpret=(backend == "interpret"))
    return y[:, :, :g].reshape(b, nh, v_pool.shape[-1])


def _attn_paged_fused(q, k_pool, v_pool, pt, pos, k_scale, v_scale,
                      logit_scale, backend):
    tp = _attn_shard(backend, q.shape[1], k_pool.shape[2])
    if tp is None:
        return _attn_paged_run(q, k_pool, v_pool, pt, pos, k_scale, v_scale,
                               logit_scale, backend)
    mesh, axis = tp
    dp = _dp_axes(mesh, axis, q.shape[0])
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    qspec = PartitionSpec(bspec, axis, None)
    # the pool is global (slots share it): kv heads shard on the model
    # axis exactly like the contiguous cache, pages replicate over data
    poolspec = PartitionSpec(None, None, axis, None)
    spoolspec = PartitionSpec(None, None, axis)
    ptspec = PartitionSpec(bspec, None)
    pspec = PartitionSpec(bspec)

    def body(ql, kl, vl, ptl, posl, ksl, vsl):
        return _attn_paged_run(ql, kl, vl, ptl, posl, ksl, vsl, logit_scale,
                               backend)

    if k_scale is None:
        return jax.shard_map(
            lambda ql, kl, vl, ptl, posl: body(ql, kl, vl, ptl, posl, None,
                                               None),
            mesh=mesh, in_specs=(qspec, poolspec, poolspec, ptspec, pspec),
            out_specs=qspec, check_vma=False,
        )(q, k_pool, v_pool, pt, pos)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qspec, poolspec, poolspec, ptspec, pspec, spoolspec,
                  spoolspec),
        out_specs=qspec, check_vma=False,
    )(q, k_pool, v_pool, pt, pos, k_scale, v_scale)


# ---- paged MLA decode ----


def _attn_mla_paged_run(q_lat, q_rope, c_pool, k_rope_pool, pt, pos,
                        c_scale, logit_scale, backend):
    """q_lat (b,nh,L) / q_rope (b,nh,R) vs c_pool (P,ps,L) +
    k_rope_pool (P,ps,R) [+ c_scale pool (P,ps)] through pt (b,np) →
    weighted latent (b,nh,L) f32."""
    b, nh, _ = q_lat.shape
    ps = c_pool.shape[1]
    nh8 = _round_up(nh, DECODE_ROWS)
    qlp = _pad_axis(q_lat, 1, nh8)
    qrp = _pad_axis(q_rope, 1, nh8)
    cap = pt.shape[1] * ps
    y = attn_decode_mla_paged_pallas(
        pt, qlp, qrp, c_pool, k_rope_pool, _decode_kmask(pos, cap), c_scale,
        logit_scale=float(logit_scale), interpret=(backend == "interpret"))
    return y[:, :nh]


def _attn_mla_paged_fused(q_lat, q_rope, c_pool, k_rope_pool, pt, pos,
                          c_scale, logit_scale, backend):
    tp = _attn_shard(backend, q_lat.shape[1], q_lat.shape[1])
    if tp is None:
        return _attn_mla_paged_run(q_lat, q_rope, c_pool, k_rope_pool, pt,
                                   pos, c_scale, logit_scale, backend)
    mesh, axis = tp
    dp = _dp_axes(mesh, axis, q_lat.shape[0])
    bspec = dp if len(dp) > 1 else (dp[0] if dp else None)
    qspec = PartitionSpec(bspec, axis, None)    # heads shard
    poolspec = PartitionSpec(None, None, None)  # latent pool replicates
    spoolspec = PartitionSpec(None, None)
    ptspec = PartitionSpec(bspec, None)
    pspec = PartitionSpec(bspec)

    def body(qll, qrl, cl, krl, ptl, posl, csl):
        return _attn_mla_paged_run(qll, qrl, cl, krl, ptl, posl, csl,
                                   logit_scale, backend)

    if c_scale is None:
        return jax.shard_map(
            lambda qll, qrl, cl, krl, ptl, posl: body(qll, qrl, cl, krl,
                                                      ptl, posl, None),
            mesh=mesh,
            in_specs=(qspec, qspec, poolspec, poolspec, ptspec, pspec),
            out_specs=qspec, check_vma=False,
        )(q_lat, q_rope, c_pool, k_rope_pool, pt, pos)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qspec, qspec, poolspec, poolspec, ptspec, pspec,
                  spoolspec),
        out_specs=qspec, check_vma=False,
    )(q_lat, q_rope, c_pool, k_rope_pool, pt, pos, c_scale)


# ---- public entry point ----


def qattention(kind: str, *args, logit_scale: float,
               backend: str | None = None,
               tiles: tuple[int, int] | None = None) -> jnp.ndarray:
    """Unified fused-attention entry point (see the section comment).

    kind="prefill":       qattention("prefill", q, k, v, positions, ...)
    kind="chunk_prefill": qattention("chunk_prefill", q, k, v, qpos,
                                     kpos, ...)
    kind="decode":        qattention("decode", q, k, v, pos,
                                     k_scale=None, v_scale=None, ...)
    kind="mla_decode":    qattention("mla_decode", q_lat, q_rope, c,
                                     k_rope, pos, c_scale=None, ...)
    kind="paged_decode":  qattention("paged_decode", q, k_pool, v_pool,
                                     pt, pos, k_scale=None,
                                     v_scale=None, ...)
    kind="paged_mla_decode":
                          qattention("paged_mla_decode", q_lat, q_rope,
                                     c_pool, k_rope_pool, pt, pos,
                                     c_scale=None, ...)

    Fused backends (pallas/interpret) run the Pallas kernels with
    pad-to-tile and optional shard_map; ``ref``/``dense`` run the
    materializing oracles from :mod:`repro.kernels.ref` — numerically the
    same contract, and the parity reference the tests pin the kernels to.
    Results are f32; callers cast.  Its ops run in the named scope
    ``qattention_<kind>``.
    """
    if kind not in _ATTN_KINDS:
        raise ValueError(f"unknown attention kind {kind!r}; "
                         f"expected one of {_ATTN_KINDS}")
    with jax.named_scope(f"qattention_{kind}"):
        return _qattention(kind, args, logit_scale, backend, tiles)


def _qattention(kind, args, logit_scale, backend, tiles):
    backend = _resolve(backend)
    if kind == "prefill":
        q, k, v, positions = args
        if backend in _FUSED:
            return _attn_prefill_qdisp(q, k, v, positions,
                                       float(logit_scale), backend, tiles)
        return ref.attn_prefill_ref(q, k, v, positions, float(logit_scale))
    if kind == "chunk_prefill":
        q, k, v, qpos, kpos = args
        if backend in _FUSED:
            return _attn_chunk_fused(q, k, v, qpos, kpos,
                                     float(logit_scale), backend, tiles)
        return ref.attn_chunk_prefill_ref(q, k, v, qpos, kpos,
                                          float(logit_scale))
    if kind == "paged_decode":
        q, k_pool, v_pool, pt, pos = args[:5]
        k_scale = args[5] if len(args) > 5 else None
        v_scale = args[6] if len(args) > 6 else None
        if backend in _FUSED:
            return _attn_paged_fused(q, k_pool, v_pool, pt, pos, k_scale,
                                     v_scale, float(logit_scale), backend)
        return ref.attn_decode_paged_ref(pt, q, k_pool, v_pool, pos,
                                         k_scale, v_scale,
                                         float(logit_scale))
    if kind == "paged_mla_decode":
        q_lat, q_rope, c_pool, k_rope_pool, pt, pos = args[:6]
        c_scale = args[6] if len(args) > 6 else None
        if backend in _FUSED:
            return _attn_mla_paged_fused(q_lat, q_rope, c_pool, k_rope_pool,
                                         pt, pos, c_scale,
                                         float(logit_scale), backend)
        return ref.attn_mla_decode_paged_ref(pt, q_lat, q_rope, c_pool,
                                             k_rope_pool, pos, c_scale,
                                             float(logit_scale))
    if kind == "decode":
        q, k, v, pos = args[:4]
        k_scale = args[4] if len(args) > 4 else None
        v_scale = args[5] if len(args) > 5 else None
        if backend in _FUSED:
            return _attn_decode_fused(q, k, v, pos, k_scale, v_scale,
                                      float(logit_scale), backend, tiles)
        return ref.attn_decode_ref(q, k, v, pos, k_scale, v_scale,
                                   float(logit_scale))
    q_lat, q_rope, c, k_rope, pos = args[:5]
    c_scale = args[5] if len(args) > 5 else None
    if backend in _FUSED:
        return _attn_mla_fused(q_lat, q_rope, c, k_rope, pos, c_scale,
                               float(logit_scale), backend, tiles)
    return ref.attn_mla_decode_ref(q_lat, q_rope, c, k_rope, pos, c_scale,
                                   float(logit_scale))


_ATTN_CANDIDATES = {
    "prefill": ((128, 128), (128, 256), (256, 128), (64, 128), (128, 512)),
    "chunk_prefill": ((128, 128), (128, 256), (64, 128), (64, 256),
                      (128, 512)),
    "decode": ((DECODE_ROWS, 128), (DECODE_ROWS, 256), (DECODE_ROWS, 512)),
    "mla_decode": ((DECODE_ROWS, 128), (DECODE_ROWS, 256),
                   (DECODE_ROWS, 512)),
    # paged decode has no tile freedom (paged GQA fixes its block from the
    # page size, paged MLA walks single pages); a single sentinel candidate
    # still times + registers the autotune key so paged launches are
    # attributable in the persisted table
    "paged_decode": ((DECODE_ROWS, 0),),
    "paged_mla_decode": ((DECODE_ROWS, 0),),
}


def autotune_qattention(kind: str, *args, logit_scale: float,
                        backend: str | None = None, candidates=None,
                        iters: int = 3):
    """Time candidate (row-tile, kv-tile) pairs through :func:`qattention`
    and register the winner under the attention autotune key (persisted via
    ``REPRO_AUTOTUNE_CACHE`` like every other entry).  Returns
    ``(best, {tiles: seconds})``; ``(None, {})`` off the fused backends.
    """
    backend = _resolve(backend)
    if backend not in _FUSED:
        return None, {}
    if kind == "prefill":
        q, k = args[0], args[1]
        seq, heads, depth, kv_dtype = q.shape[1], q.shape[2], q.shape[3], \
            k.dtype
    elif kind == "chunk_prefill":
        q, k = args[0], args[1]
        seq, heads, depth, kv_dtype = k.shape[1], q.shape[2], q.shape[3], \
            k.dtype
    elif kind == "decode":
        q, k = args[0], args[1]
        seq, heads, depth, kv_dtype = k.shape[1], q.shape[1], q.shape[2], \
            k.dtype
    elif kind == "paged_decode":
        q, k_pool, pt = args[0], args[1], args[3]
        seq = pt.shape[1] * k_pool.shape[1]
        heads, depth, kv_dtype = q.shape[1], q.shape[2], k_pool.dtype
    elif kind == "paged_mla_decode":
        q_lat, c_pool, pt = args[0], args[2], args[4]
        seq = pt.shape[1] * c_pool.shape[1]
        heads, depth, kv_dtype = q_lat.shape[1], q_lat.shape[2], c_pool.dtype
    else:
        q_lat, c = args[0], args[2]
        seq, heads, depth, kv_dtype = c.shape[1], q_lat.shape[1], \
            q_lat.shape[2], c.dtype
    timings: dict[tuple, float] = {}
    for cand in candidates or _ATTN_CANDIDATES[kind]:
        fn = jax.jit(lambda *a, c=tuple(cand): qattention(
            kind, *a, logit_scale=logit_scale, backend=backend, tiles=c))
        try:
            fn(*args).block_until_ready()
        except (ValueError, jax.errors.JaxRuntimeError):
            continue
        t0 = time.perf_counter()
        for _ in range(iters):
            fn(*args).block_until_ready()
        timings[tuple(cand)] = (time.perf_counter() - t0) / iters
    if not timings:
        return None, {}
    best = min(timings, key=timings.get)
    register_tiles(_ATTN_METHOD[kind], seq, heads, depth, _ATTN_CODEBOOK,
                   kv_dtype, (best[0], best[1], 1))
    save_autotune_table()
    return best, timings


# ---------------------------------------------------------------------------
# Autotuner (consulted by benchmarks/bench_kernels.py)
# ---------------------------------------------------------------------------

_DEFAULT_CANDIDATES = (
    (128, 256, 512), (128, 128, 512), (128, 256, 256),
    (64, 128, 256), (32, 128, 512), (8, 128, 256),
)


def autotune_qmatmul(params, x, spec, n, m, *, backend=None,
                     candidates=None, iters: int = 3):
    """Time candidate tilings through :func:`qmatmul`, register the winner.

    Returns ``(best_tiles, {tiles: seconds})``.  On the ``ref``/``dense``
    backends there is nothing to tune — returns ``(None, {})``.  Lookups are
    trace-time: autotune before jitting the consumer of the table.
    """
    backend = _resolve(backend)
    if backend not in _FUSED or not _fused_supported(params, spec):
        return None, {}  # nothing fused to tune (dense/ref path ignores tiles)
    method = "blockwise" if spec.method != "lords" else "lords"
    kdim = x.shape[-1]
    bs = None
    if method == "blockwise":
        bs = _block_operands(params, m)[2]
    timings: dict[tuple, float] = {}
    mdim = int(np.prod(x.shape[:-1]))
    # fused forwards run (and look tiles up) in compute dtype, not x.dtype
    key_dtype = jnp.dtype(spec.compute_dtype)
    for cand in candidates or _DEFAULT_CANDIDATES:
        bm, bn, bk = cand
        if bs is not None and bk % bs and bs % bk:
            continue
        fn = jax.jit(lambda xx, c=cand: qmatmul(
            params, xx, spec, n, m, backend=backend, tiles=c))
        try:
            fn(x).block_until_ready()  # compile + warm
        except (ValueError, jax.errors.JaxRuntimeError):
            # tiling rejected by the kernel's shape checks (ValueError) or by
            # the Mosaic/XLA compiler-runtime on device: skip this candidate
            continue
        t0 = time.perf_counter()
        for _ in range(iters):
            fn(x).block_until_ready()
        timings[cand] = (time.perf_counter() - t0) / iters
    if not timings:
        return None, {}
    best = min(timings, key=timings.get)
    register_tiles(method, mdim, n, kdim, spec.codebook, key_dtype, best,
                   block_size=bs)
    save_autotune_table()  # no-op unless REPRO_AUTOTUNE_CACHE is set
    return best, timings


def _diff_keys(spec) -> tuple[str, ...]:
    """Param keys that receive gradients through the fused VJPs."""
    if spec.method == "lords":
        return ("w", "b", "a") if spec.mode == "qat" else ("b", "a")
    return ("s_blk",)


def autotune_qmatmul_bwd(params, x, spec, n, m, *, backend=None,
                         candidates=None, iters: int = 3):
    """Tune the fused *backward* kernels (transposed matmul + grad
    reduction) by timing ``jax.grad`` through :func:`qmatmul` with each
    candidate registered under the transposed key (``lords_t`` /
    ``blockwise_t``), then register the winner.  Entries persist through
    the same ``REPRO_AUTOTUNE_CACHE`` file as forward tiles.

    Returns ``(best_tiles, {tiles: seconds})``; ``(None, {})`` when the
    spec has no fused path or the backend isn't fused.
    """
    backend = _resolve(backend)
    if backend not in _FUSED or not _fused_supported(params, spec):
        return None, {}
    method = "lords_t" if spec.method == "lords" else "blockwise_t"
    kdim = x.shape[-1]
    mdim = int(np.prod(x.shape[:-1]))
    bs = None
    if method == "blockwise_t":
        bs = _block_operands(params, m)[2]
    keys = _diff_keys(spec)
    operands = tuple(params[kk] for kk in keys)
    key_dtype = jnp.float32  # backward kernels always accumulate in f32
    # candidates are staged into the live table; remember any pre-existing
    # entry (cache-loaded or previously tuned) so total failure restores it
    prev = lookup_tiles(method, mdim, n, kdim, spec.codebook, key_dtype, bs)

    def loss(t, xx):
        p = dict(params, **dict(zip(keys, t)))
        return jnp.sum(qmatmul(p, xx, spec, n, m, backend=backend) ** 2)

    timings: dict[tuple, float] = {}
    for cand in candidates or _DEFAULT_CANDIDATES:
        bm, bn, bk = cand
        if bs is not None and bk % bs and bs % bk:
            continue
        # the bwd consults the table at trace time: stage the candidate,
        # trace, and drop it again if the kernels reject the tiling
        register_tiles(method, mdim, n, kdim, spec.codebook, key_dtype, cand,
                       block_size=bs)
        fn = jax.jit(jax.grad(loss, argnums=(0, 1)))
        try:
            jax.block_until_ready(fn(operands, x))
        except (ValueError, jax.errors.JaxRuntimeError):
            _AUTOTUNE.pop(
                autotune_key(method, mdim, n, kdim, spec.codebook, key_dtype,
                             bs), None)
            continue
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(operands, x))
        timings[cand] = (time.perf_counter() - t0) / iters
    if not timings:
        if prev is not None:
            register_tiles(method, mdim, n, kdim, spec.codebook, key_dtype,
                           prev, block_size=bs)
        return None, {}
    best = min(timings, key=timings.get)
    register_tiles(method, mdim, n, kdim, spec.codebook, key_dtype, best,
                   block_size=bs)
    save_autotune_table()
    return best, timings
