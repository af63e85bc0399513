"""Full language model: embed → (scanned periods of heterogeneous blocks) → head.

Layer-stack layout
------------------
``cfg.layer_pattern`` (+ MoE interleave) defines a *period* of heterogeneous
blocks (e.g. Jamba: 1×attn + 7×mamba, MoE every 2nd layer).  Layers are
initialized per period and stacked along a leading 'layers' axis, then the
forward is one ``lax.scan`` over periods with the period body unrolled —
heterogeneous architectures keep O(period) HLO size instead of O(num_layers).
Leading dense layers (``cfg.lead_layers``, DeepSeek's
``first_k_dense_replace``) sit outside the periods in a stack of their own,
``params['lead']``; caches and page pools then hold ``{'lead', 'layers'}``.

Modes
-----
  * ``forward_train(params, batch)``  -> (loss, metrics); chunked vocab loss
  * ``forward_prefill(params, tokens, cache)`` -> (last-token logits, cache)
  * ``forward_decode(params, token, cache, pos)`` -> (logits, cache)

Every linear is a quantized linear (cfg.quant) — LoRDS PEFT/QAT/frozen or any
baseline.  VLM/audio archs (`input_kind='embeddings'`) take pre-computed
frontend embeddings (the frontend itself is stubbed per assignment).

The serving forwards name their parts with ``jax.named_scope``: ``embed``,
``attn`` (the mixer with its norm and residual; ``mamba``/``mlstm``/``slstm``
for recurrent mixers), ``mlp`` (``dense_mlp`` in a leading dense layer; an
expert layer's ``router``, ``experts`` and ``shared_experts`` inside it)
and ``final_norm_head``.  Serving runs expert layers through the dropless
grouped layer over the live rows; training through the capacity layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.common import (
    P,
    dense_init,
    f32_einsum,
    rmsnorm,
    rmsnorm_init,
    shard,
    stack_periods,
)

__all__ = [
    "model_init", "cache_init", "forward_train", "forward_prefill",
    "forward_decode",
    "paged_cache_init", "forward_decode_paged", "forward_prefill_chunk",
]


# ---------------------------------------------------------------------------
# per-block init/apply
# ---------------------------------------------------------------------------

_MIXER_INIT = {
    "attn": lambda key, cfg, quant: (
        attn.mla_init(key, cfg, quant) if cfg.attn_kind == "mla"
        else attn.gqa_init(key, cfg, quant)),
    "mamba": ssm.mamba_init,
    "mlstm": ssm.mlstm_init,
    "slstm": ssm.slstm_init,
}


def _block_init(key, cfg, mixer_kind, mlp_kind):
    k1, k2 = jax.random.split(key)
    blk = {
        "ln1": rmsnorm_init(cfg.d_model),
        "mixer": _MIXER_INIT[mixer_kind](k1, cfg, cfg.quant),
    }
    if mlp_kind == "dense":
        blk["ln2"] = rmsnorm_init(cfg.d_model)
        blk["mlp"] = moe_mod.dense_mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.quant)
    elif mlp_kind == "moe":
        blk["ln2"] = rmsnorm_init(cfg.d_model)
        blk["mlp"] = moe_mod.moe_init(k2, cfg, cfg.quant)
    return blk


def _mixer_train(blk, h, cfg, mixer_kind, positions):
    q = cfg.quant
    if mixer_kind == "attn":
        if cfg.attn_kind == "mla":
            return attn.mla_train(blk, h, cfg, q, positions)
        return attn.gqa_train(blk, h, cfg, q, positions)
    if mixer_kind == "mamba":
        return ssm.mamba_train(blk, h, cfg, q)
    if mixer_kind == "mlstm":
        return ssm.mlstm_train(blk, h, cfg, q)
    return ssm.slstm_train(blk, h, cfg, q)


def _block_train(blk, x, cfg, kind, positions):
    mixer_kind, mlp_kind = kind
    h = rmsnorm(blk["ln1"], x, cfg.norm_eps)
    x = x + _mixer_train(blk["mixer"], h, cfg, mixer_kind, positions)
    aux = jnp.zeros((), jnp.float32)
    if mlp_kind == "dense":
        h = rmsnorm(blk["ln2"], x, cfg.norm_eps)
        x = x + moe_mod.dense_mlp_apply(blk["mlp"], h, cfg.d_model, cfg.d_ff,
                                        cfg.quant)
    elif mlp_kind == "moe":
        h = rmsnorm(blk["ln2"], x, cfg.norm_eps)
        y, aux = moe_mod.moe_apply(blk["mlp"], h, cfg, cfg.quant)
        x = x + y
    x = shard(x, "batch", "seq", None)
    return x, aux


# ---------------------------------------------------------------------------
# layer groups: the leading dense layers, then the scanned periods
# ---------------------------------------------------------------------------

_LEAD_KINDS = (("attn", "dense"),)


def _groups(cfg):
    """(key, kinds, count) of each stacked group of layers: the leading
    dense layers (``params['lead']``, one layer a step) where the config
    has them, then the periods (``params['layers']``)."""
    out = []
    if cfg.lead_layers:
        out.append(("lead", _LEAD_KINDS, cfg.lead_layers))
    out.append(("layers", tuple(cfg.layer_kinds()), cfg.num_periods))
    return out


def _split_state(cfg, state) -> dict:
    """A cache or pool tree by group key; a config without leading dense
    layers keeps its tree flat (the periods' stack)."""
    return dict(state) if cfg.lead_layers else {"layers": state}


def _join_state(cfg, parts: dict):
    return parts if cfg.lead_layers else parts["layers"]


def _stack_layers(cfg, init_one):
    """``init_one(kinds, i)`` -> one layer's tree for each group, stacked
    along a leading 'layers' axis, in ``_split_state`` layout."""
    parts = {}
    for key, kinds, count in _groups(cfg):
        parts[key] = stack_periods([init_one(kinds, i)
                                    for i in range(count)])
    return parts


def _scan_layers(cfg, body, carry, xs, count):
    """``lax.scan`` of ``body`` over ``count`` stacked layers, or the same
    unrolled (cost-analysis probes; XLA counts loop bodies once)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    outs = []
    for p in range(count):
        carry, out = body(carry, _index_period(xs, p))
        outs.append(out)
    return carry, jax.tree.map(lambda *ls: jnp.stack(ls, 0), *outs)


def _serve_layers(params, cfg, x, state, layer, *, remat=False):
    """Every layer of a serving pass: ``layer(blk, x, kind, blk_state,
    mlp_scope) -> (x, new_blk_state, stats)`` over each group.  Returns
    (x, new state, stats): the grouped expert layers' counters summed over
    the layers (``moe.EXPERT_STATS``; None without experts)."""
    parts = _split_state(cfg, state)
    new_parts = {}
    stats = jnp.zeros((len(moe_mod.EXPERT_STATS),), jnp.int32) \
        if cfg.moe is not None else None
    for key, kinds, count in _groups(cfg):
        scope = "dense_mlp" if key == "lead" else "mlp"

        def body(carry, inp, kinds=kinds, scope=scope):
            x, st = carry
            lp, ls = inp
            new = {}
            for i, kind in enumerate(kinds):
                x, new[f"blk{i}"], s_i = layer(lp[f"blk{i}"], x, kind,
                                               ls[f"blk{i}"], scope)
                if s_i is not None:
                    st = st + s_i
            return (x, st), new

        if remat:
            body = jax.checkpoint(body, policy=_remat_policy(cfg))
        (x, stats), new_parts[key] = _scan_layers(
            cfg, body, (x, stats), (params[key], parts[key]), count)
    return x, _join_state(cfg, new_parts), stats


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

_MIXER_CACHE = {
    "mamba": lambda cfg, b, cap: ssm.mamba_cache_init(cfg, b),
    "mlstm": lambda cfg, b, cap: ssm.mlstm_cache_init(cfg, b),
    "slstm": lambda cfg, b, cap: ssm.slstm_cache_init(cfg, b),
}


def _block_cache(cfg, mixer_kind, batch, capacity):
    if mixer_kind == "attn":
        if cfg.attn_kind == "mla":
            return attn.mla_cache_init(cfg, batch, capacity)
        return attn.gqa_cache_init(cfg, batch, capacity)
    return _MIXER_CACHE[mixer_kind](cfg, batch, capacity)


def cache_init(cfg, batch, capacity):
    """Stacked (num_periods-leading) P-tree of per-layer decode caches
    (the leading dense layers' under ``lead``, the periods' under
    ``layers``, where the config has leading dense layers)."""
    return _join_state(cfg, _stack_layers(cfg, lambda kinds, _: {
        f"blk{i}": _block_cache(cfg, k[0], batch, capacity)
        for i, k in enumerate(kinds)}))


def paged_cache_init(cfg, total_pages, page_size):
    """Stacked per-layer page pools (the paged analogue of `cache_init`).

    Paged serving needs every mixer to be a page-table reader, so it is
    attention-only: recurrent mixers (mamba/xlstm) keep O(1) state that
    the fixed-capacity path already serves without a cache window."""
    kinds = cfg.layer_kinds()
    if any(k[0] != "attn" for k in kinds):
        raise ValueError(
            "paged serving requires an attention-only layer stack; "
            f"got mixers {sorted({k[0] for k in kinds})}")
    pool_init = (attn.mla_paged_cache_init if cfg.attn_kind == "mla"
                 else attn.gqa_paged_cache_init)
    return _join_state(cfg, _stack_layers(cfg, lambda kinds, _: {
        f"blk{i}": pool_init(cfg, total_pages, page_size)
        for i in range(len(kinds))}))


def _mlp_residual(blk, x, cfg, mlp_kind, live=None, scope="mlp"):
    """Shared post-mixer MLP residual (inference paths discard moe aux).
    Expert layers run the dropless grouped layer over the ``live`` rows
    (b, s) (None: all), or the shard_map layer where the config asks for
    it.  Returns (x, expert stats or None)."""
    q = cfg.quant
    stats = None
    with jax.named_scope(scope):
        if mlp_kind == "dense":
            h = rmsnorm(blk["ln2"], x, cfg.norm_eps)
            x = x + moe_mod.dense_mlp_apply(blk["mlp"], h, cfg.d_model,
                                            cfg.d_ff, q)
        elif mlp_kind == "moe":
            h = rmsnorm(blk["ln2"], x, cfg.norm_eps)
            if cfg.moe.dispatch == "shard_map":
                y, _ = moe_mod.moe_apply(blk["mlp"], h, cfg, q)
            else:
                y, stats = moe_mod.moe_apply_grouped(blk["mlp"], h, cfg, q,
                                                     live)
            x = x + y
    return x, stats


def _block_decode(blk, x, cfg, kind, cache, pos, scope="mlp"):
    mixer_kind, mlp_kind = kind
    q = cfg.quant
    with jax.named_scope(mixer_kind):
        h = rmsnorm(blk["ln1"], x, cfg.norm_eps)
        if mixer_kind == "attn":
            if cfg.attn_kind == "mla":
                y, cache = attn.mla_decode(blk["mixer"], h, cfg, q, cache,
                                           pos)
            else:
                y, cache = attn.gqa_decode(blk["mixer"], h, cfg, q, cache,
                                           pos)
        elif mixer_kind == "mamba":
            y, cache = ssm.mamba_decode(blk["mixer"], h, cfg, q, cache, pos)
        elif mixer_kind == "mlstm":
            y, cache = ssm.mlstm_decode(blk["mixer"], h, cfg, q, cache, pos)
        else:
            y, cache = ssm.slstm_decode(blk["mixer"], h, cfg, q, cache, pos)
        x = x + y
    x, stats = _mlp_residual(blk, x, cfg, mlp_kind, scope=scope)
    return x, cache, stats


def _block_prefill(blk, x, cfg, kind, cache, positions, scope="mlp"):
    mixer_kind, mlp_kind = kind
    q = cfg.quant
    with jax.named_scope(mixer_kind):
        h = rmsnorm(blk["ln1"], x, cfg.norm_eps)
        if mixer_kind == "attn":
            if cfg.attn_kind == "mla":
                y, cache = attn.mla_prefill(blk["mixer"], h, cfg, q,
                                            positions, cache)
            else:
                y, cache = attn.gqa_prefill(blk["mixer"], h, cfg, q,
                                            positions, cache)
        else:
            # recurrent mixers: run the train path, then rebuild the final
            # state by a single decode step is wasteful; instead run train
            # path and keep zero states (prefill for SSM archs is exercised
            # via train path).
            y = _mixer_train(blk["mixer"], h, cfg, mixer_kind, positions)
        x = x + y
    x, stats = _mlp_residual(blk, x, cfg, mlp_kind, positions >= 0, scope)
    return x, cache, stats


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------


def model_init(key, cfg):
    keys = jax.random.split(key, cfg.num_periods + cfg.lead_layers + 3)
    lead_keys = keys[cfg.num_periods:cfg.num_periods + cfg.lead_layers]

    def init_one(kinds, i):
        k = lead_keys[i] if kinds is _LEAD_KINDS else keys[i]
        pkeys = jax.random.split(k, len(kinds))
        return {f"blk{j}": _block_init(pkeys[j], cfg, *kinds[j])
                for j in range(len(kinds))}

    params = {**_stack_layers(cfg, init_one),
              "final_norm": rmsnorm_init(cfg.d_model)}
    if cfg.input_kind == "tokens":
        params["embed"] = dense_init(
            keys[-1], (cfg.padded_vocab, cfg.d_model),
            ("embed_vocab", "embed"), dtype=jnp.bfloat16, scale=0.02)
    if not cfg.tie_embeddings or cfg.input_kind != "tokens":
        params["head"] = dense_init(
            keys[-2], (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
            dtype=jnp.bfloat16, scale=0.02)
    return params


def _embed_in(params, cfg, batch):
    if cfg.input_kind == "tokens":
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    else:
        x = batch["embeds"].astype(jnp.bfloat16)
    return shard(x, "batch", "seq", None)


def _head_matrix(params, cfg):
    return params["head"] if "head" in params else params["embed"]


def _final_norm_head(params, cfg, x, live=None):
    """Final norm and head: x (b, t, d) -> f32 logits (b, t, Vp).  With
    ``live`` (b, t) positions, only each row's last live token (t = 1)."""
    with jax.named_scope("final_norm_head"):
        if live is not None:
            last = jnp.argmax(live, axis=1)
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        head = _head_matrix(params, cfg)
        return f32_einsum("btd,vd->btv", x.astype(head.dtype), head)


def _embed_step(params, cfg, batch):
    """Decode-step input (b, 1, d): the token's embedding or the given
    embedding."""
    with jax.named_scope("embed"):
        if cfg.input_kind == "tokens":
            return jnp.take(params["embed"], batch["tokens"][:, None], axis=0)
        return batch["embeds"].astype(jnp.bfloat16)


def _remat_policy(cfg):
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return jax.checkpoint_policies.nothing_saveable


def _index_period(tree, i):
    return jax.tree.map(lambda v: v[i], tree)


def _scan_train(params, cfg, x, positions):
    aux = jnp.zeros((), jnp.float32)
    for key, kinds, count in _groups(cfg):
        def period_body(carry, layer_params, kinds=kinds):
            x, aux = carry
            for i, kind in enumerate(kinds):
                x, a = _block_train(layer_params[f"blk{i}"], x, cfg, kind,
                                    positions)
                aux = aux + a
            return (x, aux), None

        body = period_body
        if cfg.remat:
            body = jax.checkpoint(period_body, policy=_remat_policy(cfg))
        if cfg.scan_layers:
            (x, aux), _ = jax.lax.scan(body, (x, aux), params[key])
        else:  # unrolled (cost-analysis probes; XLA counts loop bodies once)
            for p in range(count):
                (x, aux), _ = body((x, aux), _index_period(params[key], p))
    return x, aux


def forward_train(params, cfg, batch):
    """batch: tokens/embeds (b,s[,d]) + labels (b,s) (-1 = masked).

    Returns (loss, metrics dict).
    """
    labels = batch["labels"]
    b, s = labels.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = _embed_in(params, cfg, batch)
    x, aux = _scan_train(params, cfg, x, positions)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)

    head = _head_matrix(params, cfg)  # (Vp, d)
    vocab = cfg.padded_vocab

    # chunked vocab loss: never materialize (b, s, V) f32 logits at once
    chunk = min(512, s)
    nc = s // chunk
    xc = jnp.moveaxis(x.reshape(b, nc, chunk, cfg.d_model), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, nc, chunk), 1, 0)

    def chunk_loss(carry, inp):
        xi, li = inp  # (b, chunk, d), (b, chunk)
        logits = f32_einsum("bcd,vd->bcv", xi.astype(head.dtype), head)
        logits = shard(logits, "batch", "seq", "vocab")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(li, 0)[..., None], axis=-1)[..., 0]
        mask = (li >= 0).astype(jnp.float32)
        nll = (logz - gold) * mask
        tot, cnt = carry
        return (tot + jnp.sum(nll), cnt + jnp.sum(mask)), None

    if cfg.remat:  # recompute per-chunk logits in backward: peak loss memory
        chunk_loss = jax.checkpoint(chunk_loss)  # is one vocab chunk
    (tot, cnt), _ = jax.lax.scan(
        chunk_loss, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, lc))
    loss = tot / jnp.maximum(cnt, 1.0)
    if cfg.moe is not None:
        loss = loss + 0.01 * aux
    return loss, {"loss": loss, "aux_loss": aux, "tokens": cnt}


def forward_prefill(params, cfg, batch, cache, positions=None):
    """Full-sequence forward filling caches; returns (last logits, cache).

    ``positions`` (b, s) int32 makes the window ragged: -1 rows are dead
    padding (masked out of attention, routed to no expert), and the
    returned logits come from each row's *last live* token instead of
    column s-1 — so a batch of mixed-length prompts prefills in one
    fixed-shape call without the padding leaking into the numerics.  None
    = the aligned arange (every row fully live, logits from the last
    column, as before)."""
    if cfg.input_kind == "tokens":
        b, s = batch["tokens"].shape
    else:
        b, s, _ = batch["embeds"].shape
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    with jax.named_scope("embed"):
        x = _embed_in(params, cfg, batch)

    def layer(blk, x, kind, cache, scope):
        return _block_prefill(blk, x, cfg, kind, cache, positions, scope)

    x, new_cache, _ = _serve_layers(params, cfg, x, cache, layer,
                                    remat=cfg.remat)
    return _final_norm_head(params, cfg, x, positions), new_cache


def forward_decode(params, cfg, batch, cache, pos):
    """One decode step.  batch: token (b,) or embed (b,1,d); pos (b,) int32."""
    x = _embed_step(params, cfg, batch)

    def layer(blk, x, kind, cache, scope):
        return _block_decode(blk, x, cfg, kind, cache, pos, scope)

    x, new_cache, _ = _serve_layers(params, cfg, x, cache, layer)
    return _final_norm_head(params, cfg, x), new_cache


def forward_decode_paged(params, cfg, batch, pools, pt, pos, live=None, *,
                         expert_stats=False):
    """One decode step against the page pools.  batch: token (b,) or embed
    (b,1,d); pt (b, np) page table; pos (b,) int32 current positions;
    ``live`` (b,) bool: the rows whose token is kept (the others route to
    no expert; None: all).  ``expert_stats`` also returns the grouped
    expert layers' counters (``moe.EXPERT_STATS``, summed over layers)."""
    x = _embed_step(params, cfg, batch)
    dec = (attn.mla_decode_paged if cfg.attn_kind == "mla"
           else attn.gqa_decode_paged)
    q = cfg.quant
    rows = None if live is None else live[:, None]

    def layer(blk, x, kind, pool, scope):
        with jax.named_scope("attn"):
            h = rmsnorm(blk["ln1"], x, cfg.norm_eps)
            y, pool = dec(blk["mixer"], h, cfg, q, pool, pt, pos)
            x = x + y
        x, stats = _mlp_residual(blk, x, cfg, kind[1], rows, scope)
        return x, pool, stats

    x, new_pools, stats = _serve_layers(params, cfg, x, pools, layer)
    out = (_final_norm_head(params, cfg, x), new_pools)
    return out + (stats,) if expert_stats else out


def forward_prefill_chunk(params, cfg, batch, pools, pt, qpos, pos0, *,
                          expert_stats=False):
    """One chunk of paged prefill.  batch: tokens (b, cs); qpos (b, cs)
    in-chunk positions (-1 = dead row, routed to no expert); pos0 (b,)
    page-aligned chunk start.

    Returns (last-live-row logits (b, 1, V), new pools) and, with
    ``expert_stats``, the grouped expert layers' counters.  The logits are
    each row's argmax(qpos) column — only meaningful for slots whose final
    prompt token is in this chunk (the scheduler samples token 1 from them
    then, and ignores them for slots still mid-prompt)."""
    with jax.named_scope("embed"):
        x = _embed_in(params, cfg, batch)
    pre = (attn.mla_prefill_chunk if cfg.attn_kind == "mla"
           else attn.gqa_prefill_chunk)
    q = cfg.quant

    def layer(blk, x, kind, pool, scope):
        with jax.named_scope("attn"):
            h = rmsnorm(blk["ln1"], x, cfg.norm_eps)
            y, pool = pre(blk["mixer"], h, cfg, q, qpos, pos0, pool, pt)
            x = x + y
        x, stats = _mlp_residual(blk, x, cfg, kind[1], qpos >= 0, scope)
        return x, pool, stats

    x, new_pools, stats = _serve_layers(params, cfg, x, pools, layer)
    out = (_final_norm_head(params, cfg, x, qpos), new_pools)
    return out + (stats,) if expert_stats else out
