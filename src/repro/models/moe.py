"""Mixture-of-Experts layer with expert parallelism.

Dispatch is sort-free scatter-based (O(T·k·d) data movement, no (T×E×C)
one-hot einsum whose FLOPs would be quadratic in tokens):

  1. router top-k over experts (f32),
  2. per-assignment slot index = rank of the token within its expert queue
     (computed with an argsort over the T·k expert ids),
  3. scatter into the (E, C, d) dispatch buffer (capacity-dropped, like
     GShard/Switch; capacity_factor controls drop rate),
  4. per-expert quantized FFN (LoRDS/baseline weights, stacked per expert),
  5. gather back + gate-weighted combine.

That capacity layer is the training path (it has a backward).  Serving
runs the dropless grouped layer (:func:`moe_apply_grouped`): assignments
of live rows sorted by expert, each expert's group padded to a row tile,
and one grouped quantized matmul over the expert stack
(:func:`repro.kernels.dispatch.qmatmul_experts`) — no assignment is ever
dropped, and dead or padding rows route nowhere.

Routing follows the config: softmax scores, top-k, the gates renormalised
to sum to 1 unless ``norm_topk_prob`` is False (then scaled by
``routed_scaling_factor``).  Shared experts (``n_shared``) are one SwiGLU
of ``n_shared × d_ff`` every row goes through, added to the routed sum.

Expert weights carry the 'expert' logical axis; the dispatch buffer is
sharding-constrained to the expert axis so GSPMD materializes the
all-to-all on the expert-parallel mesh axis.  Router aux (load-balance) loss
is returned to the caller.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import lords
from repro.models.common import P, dense_init, shard

__all__ = ["moe_init", "moe_apply", "moe_apply_grouped", "group_rows",
           "expert_row_tile", "dense_mlp_init", "dense_mlp_apply",
           "EXPERT_STATS"]

# what moe_apply_grouped counts per layer: distinct routed experts hit,
# assignments of live rows, rows the grouped matmul computes
EXPERT_STATS = ("hits", "assignments", "rows")


# ---------------------------------------------------------------------------
# dense (SwiGLU) MLP — also the per-expert FFN body
# ---------------------------------------------------------------------------


def dense_mlp_init(key, d, d_ff, quant):
    ks = jax.random.split(key, 3)
    from repro.models.common import qlinear_init

    return {
        "w_gate": qlinear_init(ks[0], d_ff, d, quant, "mlp", "embed"),
        "w_up": qlinear_init(ks[1], d_ff, d, quant, "mlp", "embed"),
        "w_down": qlinear_init(ks[2], d, d_ff, quant, "embed", "mlp"),
    }


def dense_mlp_apply(params, x, d, d_ff, quant):
    from repro.models.common import qlinear_apply

    g = qlinear_apply(params["w_gate"], x, quant, d_ff, d)
    u = qlinear_apply(params["w_up"], x, quant, d_ff, d)
    h = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
    h = shard(h.astype(x.dtype), "batch", "seq", "mlp_act")
    return qlinear_apply(params["w_down"], h, quant, d, d_ff)


# ---------------------------------------------------------------------------
# expert-stacked quantized linears (vmapped core init over the expert axis)
# ---------------------------------------------------------------------------


def _qlinear_stack_init(key, e, n, m, quant):
    """Stack of e quantized (n×m) linears; leaves get a leading 'expert' axis.

    vmapped over the expert axis — a Python loop here costs minutes of trace
    time at kimi-k2 scale (384 experts × 61 layers × 3 matrices).
    """
    keys = jax.random.split(key, e)
    init_one = lambda k: lords.init_quantized_linear(k, n, m, quant)
    stacked = jax.vmap(init_one)(keys)
    axes = lords.linear_param_specs(quant, "moe_out", "moe_in")
    return {
        k: P(v, ("expert",) + axes[k]) for k, v in stacked.items()
    }


def _qlinear_stack_apply(ptree, xd, quant, n, m, e_here):
    """Batched per-expert quantized matmul: (E, C, m) -> (E, C, n).

    vmaps the kernel-dispatch entry point over the expert axis, so each
    expert's fused dequant-matmul runs as one batched kernel invocation —
    the (E, n, m) dequantized weight stack is never materialized.

    Tensor-parallel dispatch is pinned off here: expert weights shard over
    the *expert* axis (EP), not row-wise over 'model', so the per-expert
    matmuls must stay local (and a shard_map under this vmap would be
    ill-formed anyway).
    """
    from repro.kernels import dispatch
    from repro.kernels.dispatch import qmatmul

    sliced = jax.tree.map(lambda v: v[:e_here], ptree)
    with dispatch.shard_scope(None):
        return jax.vmap(lambda p, xe: qmatmul(p, xe, quant, n, m))(sliced, xd)


def _n_experts_padded(mo):
    return max(mo.pad_experts_to or 0, mo.num_experts)


def moe_init(key, cfg, quant):
    mo, d = cfg.moe, cfg.d_model
    e_pad = _n_experts_padded(mo)
    ks = jax.random.split(key, 5)
    out = {
        "router": dense_init(ks[0], (mo.num_experts, d), ("expert", "embed"),
                             dtype=jnp.float32),
        "w_gate": _qlinear_stack_init(ks[1], e_pad, mo.d_ff, d, quant),
        "w_up": _qlinear_stack_init(ks[2], e_pad, mo.d_ff, d, quant),
        "w_down": _qlinear_stack_init(ks[3], e_pad, d, mo.d_ff, quant),
    }
    if mo.shared_ff:
        out["shared"] = dense_mlp_init(ks[4], d, mo.shared_ff, quant)
    return out


def _shared_apply(params, x, cfg, quant):
    """The shared experts' SwiGLU over every row (None without them)."""
    mo = cfg.moe
    if not mo.shared_ff:
        return None
    with jax.named_scope("shared_experts"):
        return dense_mlp_apply(params["shared"], x, cfg.d_model,
                               mo.shared_ff, quant)


def _route(params, xf, mo):
    """Shared router: returns (gates (t,k), idx (t,k), aux scalar)."""
    e, k = mo.num_experts, mo.top_k
    logits = jnp.einsum(
        "td,ed->te", xf.astype(jnp.float32),
        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    if mo.renormalise:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    elif (mo.routed_scaling_factor or 1.0) != 1.0:
        gates = gates * mo.routed_scaling_factor
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=1), axis=0)
    aux = e * jnp.sum(me * ce)
    return gates, idx, aux


def _ranks_within_expert(flat_e, e_total, tk):
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e_total), side="left")
    rank_sorted = jnp.arange(tk) - seg_start[sorted_e]
    return jnp.zeros((tk,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))


def _expert_ffn(xd, params, mo, d, quant):
    """SwiGLU over (E_local, C, d) with stacked (possibly padded) experts."""
    e_here = xd.shape[0]
    g = _qlinear_stack_apply(params["w_gate"], xd, quant, mo.d_ff, d, e_here)
    u = _qlinear_stack_apply(params["w_up"], xd, quant, mo.d_ff, d, e_here)
    h = (jax.nn.silu(g.astype(jnp.float32))
         * u.astype(jnp.float32)).astype(xd.dtype)
    return _qlinear_stack_apply(params["w_down"], h, quant, d, mo.d_ff, e_here)


def moe_apply(params, x, cfg, quant):
    """x (b,s,d) -> (y (b,s,d), aux_loss scalar): the capacity layer (or
    its shard_map form), plus the shared experts."""
    if cfg.moe.dispatch == "shard_map":
        from repro.models.moe_shardmap import moe_apply_shard_map

        y, aux = moe_apply_shard_map(params, x, cfg, quant)
    else:
        y, aux = _moe_apply_pjit(params, x, cfg, quant)
    shared = _shared_apply(params, x, cfg, quant)
    if shared is not None:
        y = y + shared
    return y, aux


def expert_row_tile(t: int, k: int, e: int) -> int:
    """Row tile of the grouped expert matmul for ``t`` rows: the mean rows
    an expert gets (t·k/e) rounded up to a power of two within [8, 128] —
    8 for decode batches, 128 for prefill chunks."""
    mean = max(-(-t * k // e), 1)
    return min(128, max(8, 1 << (mean - 1).bit_length()))


def group_rows(idx, live, e: int, rows: int):
    """Sort the assignments by expert and pad each expert's group to whole
    tiles of ``rows`` rows.

    ``idx`` (t, k) expert ids, ``live`` (t,) bool: rows that are not live
    route nowhere.  The buffer holds ``tiles = ⌈(t·k + e·(rows-1)) /
    rows⌉`` row tiles, the most any routing needs.  Returns ``pos``
    (t·k,) each assignment's row in the buffer (``tiles·rows`` for a dead
    one), ``(tile_expert, tile_row, tile_live)`` (tiles,) int32 — a tile
    past the live ones repeats the last live tile's expert and row tile —
    and ``counts`` (e,) assignments per expert."""
    t, k = idx.shape
    n_tiles = -(-(t * k + e * (rows - 1)) // rows)
    total = n_tiles * rows
    flat_e = jnp.where(live[:, None], idx, e).reshape(-1).astype(jnp.int32)
    counts = jnp.zeros((e + 1,), jnp.int32).at[flat_e].add(1)[:e]
    padded = -(-counts // rows) * rows
    ends = jnp.cumsum(padded)
    starts = ends - padded
    rank = _ranks_within_expert(flat_e, e + 1, t * k)
    pos = jnp.where(flat_e < e,
                    starts[jnp.minimum(flat_e, e - 1)] + rank, total)
    used = ends[-1] // rows
    tile = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, tile * rows, side="right"), e - 1)
    tile_live = tile < used
    last = jnp.maximum(used - 1, 0)
    tile_row = jnp.where(tile_live, tile, last).astype(jnp.int32)
    tile_expert = jnp.where(tile_live, tile_expert,
                            tile_expert[last]).astype(jnp.int32)
    return pos, (tile_expert, tile_row, tile_live.astype(jnp.int32)), counts


def moe_apply_grouped(params, x, cfg, quant, live=None):
    """Dropless serving layer: x (b, s, d), ``live`` (b, s) bool or None
    (every row live) -> (y (b, s, d), stats (3,) int32, ``EXPERT_STATS``).

    Every live row's top-k assignments are computed — none is dropped —
    in at most ``live·k + E·(rows−1)`` rows: the grouped matmul skips the
    buffer's tiles past the last live one."""
    from repro.kernels.dispatch import qmatmul_experts

    mo, d = cfg.moe, cfg.d_model
    e, k = mo.num_experts, mo.top_k
    b, s, _ = x.shape
    t = b * s
    xf = x.reshape(t, d)
    live = (jnp.ones((t,), bool) if live is None
            else live.reshape(t).astype(bool))
    rows = expert_row_tile(t, k, e)
    with jax.named_scope("router"):
        gates, idx, _ = _route(params, xf, mo)
        pos, tiles, counts = group_rows(idx, live, e, rows)
    with jax.named_scope("experts"):
        total = tiles[0].shape[0] * rows
        # the token each buffer row holds (t: the zero row, for padding)
        token = jnp.arange(t * k, dtype=jnp.int32) // k
        row_token = jnp.full((total,), t, jnp.int32).at[pos].set(
            token, mode="drop")
        xr = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])[row_token]
        grouped = tiles + (rows,)
        g = qmatmul_experts(params["w_gate"], xr, grouped, quant, mo.d_ff, d)
        u = qmatmul_experts(params["w_up"], xr, grouped, quant, mo.d_ff, d)
        h = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(x.dtype)
        yr = qmatmul_experts(params["w_down"], h, grouped, quant, d, mo.d_ff)
        yr = jnp.concatenate([yr, jnp.zeros((1, d), yr.dtype)])
        w = jnp.where(pos < total, gates.reshape(-1), 0.0)
        y = jnp.sum((yr[jnp.minimum(pos, total)].astype(jnp.float32)
                     * w[:, None]).reshape(t, k, d), axis=1)
    shared = _shared_apply(params, x, cfg, quant)
    if shared is not None:
        y = y + shared.reshape(t, d).astype(jnp.float32)
    n_assigned = jnp.sum(counts)
    stats = jnp.stack([jnp.sum(counts > 0), n_assigned,
                       jnp.sum(-(-counts // rows)) * rows]).astype(jnp.int32)
    return y.reshape(b, s, d).astype(x.dtype), stats


def _moe_apply_pjit(params, x, cfg, quant):
    mo, d = cfg.moe, cfg.d_model
    e, k = mo.num_experts, mo.top_k
    e_pad = _n_experts_padded(mo)
    b, s, _ = x.shape
    t = b * s
    xf = x.reshape(t, d)

    gates, idx, aux = _route(params, xf, mo)

    # ---- slot assignment: rank of each (token, j) within its expert ----
    flat_e = idx.reshape(-1)  # (t*k,)
    ranks = _ranks_within_expert(flat_e, e, t * k)

    cap = int(mo.capacity_factor * t * k / e + 0.5)
    cap = max(8, -(-cap // 8) * 8)  # round up to a multiple of 8
    keep = ranks < cap
    dest = jnp.where(keep, flat_e * cap + ranks, e_pad * cap)  # drops -> pad

    # ---- dispatch (scatter) ----
    src = jnp.repeat(xf, k, axis=0)  # (t*k, d) token rows per assignment
    src = shard(src, "tokens", None)
    buf = jnp.zeros((e_pad * cap + 1, d), x.dtype).at[dest].set(src)
    xd = buf[: e_pad * cap].reshape(e_pad, cap, d)
    xd = shard(xd, "expert", "capacity", None)

    yd = _expert_ffn(xd, params, mo, d, quant)
    yd = shard(yd, "expert", "capacity", None)

    # ---- combine (gather) ----
    ybuf = jnp.concatenate([yd.reshape(e_pad * cap, d),
                            jnp.zeros((1, d), yd.dtype)], axis=0)
    per_assign = ybuf[dest]  # (t*k, d); dropped slots hit the zero pad row
    per_assign = shard(per_assign, "tokens", None)
    per_assign = per_assign * gates.reshape(-1)[:, None].astype(per_assign.dtype)
    y = jnp.sum(per_assign.reshape(t, k, d), axis=1)
    return y.reshape(b, s, d).astype(x.dtype), aux
