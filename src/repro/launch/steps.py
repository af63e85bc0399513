"""pjit-able train / serve steps + the machinery to build their shardings.

``build_plan(cfg, mesh, shape_cfg, ...)`` produces a StepPlan holding
  * abstract state (ShapeDtypeStructs — nothing allocated),
  * matching NamedSharding trees (in/out),
  * the step callable (closed over cfg + activation rules),
ready for ``jax.jit(...).lower(...).compile()`` (dry-run) or real execution.

Modes:
  * train: LoRDS-PEFT by default (trainable = B/A; frozen packed Q) — the
    paper's regime and the only one that fits 1T params on 512 v5e chips;
    ``cfg.quant.mode='qat'`` switches to full STE fake-quant training.
  * prefill: full-sequence forward, fills KV/SSM caches, returns last logits.
  * decode: one token with caches (the serve_step for decode shapes).

``build_generate_plan`` wraps the decode step in an on-device
``jax.lax.scan`` over the whole generation budget: one jit, one dispatch,
donated cache — decode cost becomes kernel-bound instead of paying a host
round-trip per token (the decode fast path the paper's §4.4 speedup needs).

Every step function runs inside a ``jax.named_scope`` named from its plan
(``step_train``, ``step_prefill``, ``step_decode``, ``step_generate_g<gen>``,
``step_chunk_prefill``, ``step_paged_generate_g<gen>``), with ``sample``
around token sampling.  Scopes are op metadata: they name the ops in a
profiler trace and leave the compiled program as it is.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import peft
from repro.distributed.sharding import make_rules, tree_shardings
from repro.kernels import dispatch
from repro.models import (
    EXPERT_STATS,
    activation_rules,
    cache_init,
    forward_decode,
    forward_decode_paged,
    forward_prefill,
    forward_prefill_chunk,
    forward_train,
    model_init,
    paged_cache_init,
    split_tree,
)
from repro.optim import adamw_init, adamw_update, guarded_update

__all__ = ["StepPlan", "build_plan", "build_generate_plan", "sample_token",
           "sample_token_guarded", "NONFINITE_TOKEN",
           "build_prefill_chunk_plan", "build_paged_generate_plan"]


def _meta_backend(kernel_backend: str | None) -> str:
    """Honest meta label: an explicit backend is pinned into the step via
    backend_scope; None re-resolves at trace time, so report it as auto."""
    return kernel_backend or f"auto:{dispatch.default_backend()}"


def _meta_attention(kernel_backend: str | None) -> str:
    """Which attention body the step traces: the fused flash kernels
    (qattention routes prefill + quantized-KV decode through Pallas) or the
    portable einsum oracle."""
    return ("fused" if dispatch.fused_backend_active(kernel_backend)
            else "einsum-ref")


def _meta_sharding(mesh, rules) -> dict:
    """Layout record for the plan: mesh shape, model parallelism (the degree
    the fused qmatmuls shard over inside the step's shard_scope), and the
    policy summary (codes-shard / factors-replicate + dropped rules)."""
    return dict(rules.summary(),
                mesh={k: int(v) for k, v in dict(mesh.shape).items()},
                model_parallel=int(dict(mesh.shape).get("model", 1)))


@contextlib.contextmanager
def _step_scope(name: str, rules, kernel_backend: str | None, mesh):
    """What every step function traces under: its named scope, the plan's
    activation rules, the pinned kernel backend and the shard scope."""
    with jax.named_scope(name), activation_rules(rules.act_rules), \
            dispatch.backend_scope(kernel_backend), \
            dispatch.shard_scope(mesh):
        yield


@dataclasses.dataclass
class StepPlan:
    name: str
    step_fn: Callable
    abstract_args: tuple
    in_shardings: tuple
    out_shardings: Any
    rules: Any          # ShardingPolicy
    donate_argnums: tuple = ()
    meta: dict = dataclasses.field(default_factory=dict)


def _abstract_init(cfg, batch_example=None):
    key = jax.random.PRNGKey(0)
    ptree = jax.eval_shape(lambda k: model_init(k, cfg), key)
    return ptree


def _batch_specs(cfg, shape_cfg, mesh, rules, *, decode=False):
    b = shape_cfg.global_batch
    s = shape_cfg.seq_len
    batch_rule = rules.act_rules.get("batch")
    axes = tuple(a for a in ((batch_rule,) if isinstance(batch_rule, str)
                             else (batch_rule or ())) if a in mesh.shape)
    bsize = 1
    for a in axes:
        bsize *= mesh.shape[a]
    bspec = (axes if len(axes) > 1 else (axes[0] if axes else None)) \
        if (axes and b % max(bsize, 1) == 0) else None

    def sd(shape, dtype, spec):
        return (jax.ShapeDtypeStruct(shape, dtype),
                NamedSharding(mesh, PartitionSpec(*spec)))

    if decode:
        if cfg.input_kind == "tokens":
            tok, tok_sh = sd((b,), jnp.int32, (bspec,))
            batch = {"tokens": tok}
            bsh = {"tokens": tok_sh}
        else:
            e, e_sh = sd((b, 1, cfg.d_model), jnp.bfloat16, (bspec, None, None))
            batch = {"embeds": e}
            bsh = {"embeds": e_sh}
        pos, pos_sh = sd((b,), jnp.int32, (bspec,))
        return batch, bsh, pos, pos_sh

    if cfg.input_kind == "tokens":
        tok, tok_sh = sd((b, s), jnp.int32, (bspec, None))
        lab, lab_sh = sd((b, s), jnp.int32, (bspec, None))
        return {"tokens": tok, "labels": lab}, {"tokens": tok_sh, "labels": lab_sh}
    e, e_sh = sd((b, s, cfg.d_model), jnp.bfloat16, (bspec, None, None))
    lab, lab_sh = sd((b, s), jnp.int32, (bspec, None))
    return {"embeds": e, "labels": lab}, {"embeds": e_sh, "labels": lab_sh}


def _pick_microbatches(global_batch: int, dp: int, seq: int,
                       target_tokens: int = 8192) -> int:
    """Smallest divisor of the per-DP-shard batch that caps live tokens/device
    at ~target_tokens per microbatch (bounds the remat carry footprint)."""
    b_local = max(global_batch // max(dp, 1), 1)
    want = -(-b_local * seq // target_tokens)
    for n in range(1, b_local + 1):
        if b_local % n == 0 and n >= want:
            return n
    return b_local


def _plan_state(cfg, mesh, shape_cfg, kind, *, budget_gb, force_2d,
                seq_parallel=False):
    """Shared plan setup: sharding rules + abstract weights and their
    shardings (one code path for train / prefill / decode / generate, so
    the scan generation loop can never drift from the host-loop decode
    shardings it is parity-tested against)."""
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    seq_shard = (kind == "decode" and shape_cfg.global_batch < dp)
    rules = make_rules(cfg, mesh, kind, budget_gb=budget_gb,
                       force_2d=force_2d, seq_shard_cache=seq_shard,
                       seq_parallel=seq_parallel)
    dropped: list = []
    values, axes = split_tree(_abstract_init(cfg))
    shard_tree = tree_shardings(axes, values, rules.weight_rules, mesh, dropped)
    rules.dropped.extend(dropped)
    return rules, values, shard_tree


def _cache_state(cfg, mesh, shape_cfg, rules):
    """Abstract decode cache + its shardings (serving kinds only)."""
    cache_ptree = jax.eval_shape(
        lambda: cache_init(cfg, shape_cfg.global_batch, shape_cfg.seq_len))
    cache_vals, cache_axes = split_tree(cache_ptree)
    cache_sh = tree_shardings(cache_axes, cache_vals, rules.act_rules, mesh,
                              rules.dropped)
    return cache_vals, cache_sh


def build_plan(cfg, mesh, shape_cfg, *, lr: float = 1e-4,
               force_2d: bool | None = None, budget_gb: float = 8.0,
               num_microbatches: int | None = None,
               target_micro_tokens: int = 8192,
               seq_parallel: bool = False,
               kernel_backend: str | None = None,
               grad_guard: bool = False) -> StepPlan:
    """``kernel_backend`` pins the quantized-matmul dispatch backend for
    everything traced inside the produced step (None = ambient default:
    fused Pallas on TPU, interpret/ref per env flags elsewhere).

    ``grad_guard`` (train kind only) appends a scalar ``max_gnorm``
    argument to the step and routes the update through
    :func:`repro.optim.guarded_update`: a non-finite or
    above-threshold grad norm applies a *zero* update in-graph (params,
    moments and the step counter all keep their old values) and reports
    ``update_skipped`` in the metrics — the train loop's spike detector
    feeds the threshold and decides on checkpoint rollback."""
    kind = shape_cfg.kind
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    rules, values, shard_tree = _plan_state(
        cfg, mesh, shape_cfg, kind, budget_gb=budget_gb, force_2d=force_2d,
        seq_parallel=seq_parallel)

    if kind == "train":
        t_vals, f_vals = peft.partition(values, cfg.quant)
        t_sh, f_sh = peft.partition(shard_tree, cfg.quant)
        opt = jax.eval_shape(adamw_init, t_vals)
        rep = NamedSharding(mesh, PartitionSpec())
        opt_sh = type(opt)(mu=t_sh, nu=t_sh, step=rep)
        tgt = min(target_micro_tokens, cfg.micro_tokens)
        n_micro = (num_microbatches if num_microbatches is not None else
                   _pick_microbatches(shape_cfg.global_batch, dp,
                                      shape_cfg.seq_len, tgt))

        def train_step(trainable, frozen, opt_state, batch, max_gnorm=None):
            with _step_scope("step_train", rules, kernel_backend, mesh):
                def loss_fn(t, mb):
                    params = peft.combine(t, frozen)
                    loss, metrics = forward_train(params, cfg, mb)
                    return loss, metrics

                if n_micro == 1:
                    (loss, metrics), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(trainable, batch)
                else:
                    # gradient accumulation over microbatches (memory: remat
                    # carries scale with the microbatch, not the global batch)
                    from repro.models.common import shard as shard_act

                    def split(x):
                        x = x.reshape(n_micro, x.shape[0] // n_micro,
                                      *x.shape[1:])
                        return shard_act(x, None, "batch")
                    micro = jax.tree.map(split, batch)
                    g0 = jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), trainable)

                    def mb_body(carry, mb):
                        g_acc, loss_acc = carry
                        (loss, _), grads = jax.value_and_grad(
                            loss_fn, has_aux=True)(trainable, mb)
                        g_acc = jax.tree.map(
                            lambda a, g: a + g.astype(jnp.float32),
                            g_acc, grads)
                        return (g_acc, loss_acc + loss), None

                    (grads, loss_sum), _ = jax.lax.scan(
                        mb_body, (g0, jnp.zeros((), jnp.float32)), micro)
                    grads = jax.tree.map(lambda g: g / n_micro, grads)
                    loss = loss_sum / n_micro
                    metrics = {"loss": loss}
                if grad_guard:
                    new_t, new_opt, gnorm, ok = guarded_update(
                        trainable, grads, opt_state, lr, max_gnorm)
                else:
                    new_t, new_opt, gnorm = adamw_update(
                        trainable, grads, opt_state, lr)
                    ok = None
            metrics = dict(metrics, grad_norm=gnorm)
            if ok is not None:
                metrics["update_skipped"] = 1.0 - ok.astype(jnp.float32)
            return new_t, new_opt, metrics

        batch, batch_sh = _batch_specs(cfg, shape_cfg, mesh, rules)
        args = (t_vals, f_vals, opt, batch)
        shardings = (t_sh, f_sh, opt_sh, batch_sh)
        if grad_guard:
            args += (jax.ShapeDtypeStruct((), jnp.float32),)
            shardings += (NamedSharding(mesh, PartitionSpec()),)
        return StepPlan(
            name=f"train:{cfg.name}:{shape_cfg.name}",
            step_fn=train_step,
            abstract_args=args,
            in_shardings=shardings,
            out_shardings=(t_sh, opt_sh, None),
            rules=rules,
            donate_argnums=(0, 2),
            meta={"mode": cfg.quant.mode, "kind": kind,
                  "num_microbatches": n_micro, "grad_guard": grad_guard,
                  "kernel_backend": _meta_backend(kernel_backend),
                  "sharding": _meta_sharding(mesh, rules)},
        )

    # ---- serving ----
    cache_vals, cache_sh = _cache_state(cfg, mesh, shape_cfg, rules)

    if kind == "prefill":
        batch, batch_sh = _batch_specs(cfg, shape_cfg, mesh, rules)
        batch.pop("labels"), batch_sh.pop("labels")

        def prefill_step(params, batch, cache):
            # optional "positions" (b, s) rides in the batch dict: ragged
            # prompt lengths mask their padding out of the window (see
            # forward_prefill); absent = the aligned arange as before
            with _step_scope("step_prefill", rules, kernel_backend, mesh):
                logits, new_cache = forward_prefill(
                    params, cfg, batch, cache, batch.get("positions"))
            return logits, new_cache

        return StepPlan(
            name=f"prefill:{cfg.name}:{shape_cfg.name}",
            step_fn=prefill_step,
            abstract_args=(values, batch, cache_vals),
            in_shardings=(shard_tree, batch_sh, cache_sh),
            out_shardings=(None, cache_sh),
            rules=rules,
            donate_argnums=(2,),
            meta={"kind": kind,
                  "kernel_backend": _meta_backend(kernel_backend),
                  "attention": _meta_attention(kernel_backend),
                  "sharding": _meta_sharding(mesh, rules)},
        )

    # decode
    batch, batch_sh, pos, pos_sh = _batch_specs(
        cfg, shape_cfg, mesh, rules, decode=True)

    def decode_step(params, batch, cache, pos):
        with _step_scope("step_decode", rules, kernel_backend, mesh):
            logits, new_cache = forward_decode(params, cfg, batch, cache, pos)
        return logits, new_cache

    return StepPlan(
        name=f"decode:{cfg.name}:{shape_cfg.name}",
        step_fn=decode_step,
        abstract_args=(values, batch, cache_vals, pos),
        in_shardings=(shard_tree, batch_sh, cache_sh, pos_sh),
        out_shardings=(None, cache_sh),
        rules=rules,
        donate_argnums=(2,),
        meta={"kind": kind,
              "kernel_backend": _meta_backend(kernel_backend),
              "attention": _meta_attention(kernel_backend),
              "sharding": _meta_sharding(mesh, rules)},
    )


# ---------------------------------------------------------------------------
# On-device generation loop (single jit over the whole decode budget)
# ---------------------------------------------------------------------------


def sample_token(logits, key, temperature: float):
    """Greedy (temperature <= 0) or temperature sampling over (b, V) logits."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits.astype(jnp.float32) / temperature, axis=-1
    ).astype(jnp.int32)


NONFINITE_TOKEN = -1


def sample_token_guarded(logits, key, temperature: float):
    """:func:`sample_token` plus the serving non-finite guard: rows whose
    logits contain a NaN/Inf emit :data:`NONFINITE_TOKEN` (-1) instead of a
    garbage sample.  The engine treats -1 as a per-slot poison marker and
    quarantines only that slot — the rest of the batch keeps decoding.  On
    finite logits this is bitwise ``sample_token`` (the ``where`` is an
    identity), so clean-run parity is untouched."""
    tok = sample_token(logits, key, temperature)
    ok = jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)
    return jnp.where(ok, tok, jnp.int32(NONFINITE_TOKEN))


def build_generate_plan(cfg, mesh, shape_cfg, *, gen: int,
                        temperature: float = 0.0,
                        force_2d: bool | None = None, budget_gb: float = 8.0,
                        kernel_backend: str | None = None) -> StepPlan:
    """A StepPlan whose step runs ``gen`` decode steps as one on-device
    ``lax.scan`` — the caller dispatches once, the cache never leaves the
    device, and per-token cost is the decode kernels, not Python.

    step_fn(params, tok0, cache, pos0, key, embeds0) -> (tokens (b, gen),
    cache).  ``tok0`` (b,) seeds the loop (usually argmax of the prefill
    logits); ``pos0`` (b,) may be ragged per sequence.  ``embeds0`` is the
    fixed per-step input for ``input_kind='embeddings'`` archs (frontends
    are stubbed) and None for token models.  Donate the cache (argnums 2)
    when jitting.
    """
    rules, values, shard_tree = _plan_state(
        cfg, mesh, shape_cfg, "decode", budget_gb=budget_gb,
        force_2d=force_2d)
    cache_vals, cache_sh = _cache_state(cfg, mesh, shape_cfg, rules)
    batch, batch_sh, pos, pos_sh = _batch_specs(
        cfg, shape_cfg, mesh, rules, decode=True)
    b = shape_cfg.global_batch
    tok0 = jax.ShapeDtypeStruct((b,), jnp.int32)
    key_arg = jax.ShapeDtypeStruct((2,), jnp.uint32)
    embeds0 = batch.get("embeds")

    def generate_step(params, tok0, cache, pos0, key, embeds0=None):
        with _step_scope(f"step_generate_g{gen}", rules, kernel_backend,
                         mesh):
            def body(carry, _):
                tok, cache, pos, key = carry
                if cfg.input_kind == "tokens":
                    step_in = {"tokens": tok}
                else:
                    step_in = {"embeds": embeds0}
                logits, cache = forward_decode(params, cfg, step_in, cache,
                                               pos)
                key, sub = jax.random.split(key)
                with jax.named_scope("sample"):
                    nxt = sample_token(logits[:, -1, : cfg.vocab_size], sub,
                                       temperature)
                return (nxt, cache, pos + 1, key), nxt

            (_, cache, _, _), toks = jax.lax.scan(
                body, (tok0, cache, pos0, key), None, length=gen)
        return jnp.moveaxis(toks, 0, 1), cache  # (b, gen)

    return StepPlan(
        name=f"generate:{cfg.name}:{shape_cfg.name}:g{gen}",
        step_fn=generate_step,
        abstract_args=(values, tok0, cache_vals, pos, key_arg, embeds0),
        in_shardings=(shard_tree, pos_sh, cache_sh, pos_sh, None,
                      batch_sh.get("embeds")),
        out_shardings=(None, cache_sh),
        rules=rules,
        donate_argnums=(2,),
        meta={"kind": "generate", "gen": gen, "temperature": temperature,
              "kernel_backend": _meta_backend(kernel_backend),
              "attention": _meta_attention(kernel_backend),
              "sharding": _meta_sharding(mesh, rules)},
    )


# ---------------------------------------------------------------------------
# Paged serving steps (continuous-batching engine; launch/engine.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _PagedShape:
    """Minimal ShapeCfg stand-in for the paged plans (they key off explicit
    slots/pages arguments, not a named benchmark shape)."""
    seq_len: int
    global_batch: int
    kind: str
    name: str = "paged"


def _pool_state(cfg, mesh, rules, total_pages, page_size):
    """Abstract page pools + shardings (pages replicate over data, kv heads
    keep their model rule — see gqa_paged_cache_init)."""
    pools_ptree = jax.eval_shape(
        lambda: paged_cache_init(cfg, total_pages, page_size))
    vals, axes = split_tree(pools_ptree)
    sh = tree_shardings(axes, vals, rules.act_rules, mesh, rules.dropped)
    return vals, sh


def build_prefill_chunk_plan(cfg, mesh, *, slots: int, chunk: int,
                             total_pages: int, page_size: int,
                             max_pages: int, temperature: float = 0.0,
                             force_2d: bool | None = None,
                             budget_gb: float = 8.0,
                             kernel_backend: str | None = None) -> StepPlan:
    """One fixed-shape chunk of paged prefill over the whole slot batch.

    step_fn(params, tokens (slots, chunk), pools, pt (slots, max_pages),
    qpos (slots, chunk), pos0 (slots,), key) -> (tok1 (slots,), pools).
    Dead slots (qpos all -1, pt row all zeros) write only the dummy page
    and produce garbage tok1 the scheduler ignores; ``tok1`` is each row's
    token sampled from its last live logits — the first generated token for
    slots whose prompt ends in this chunk.  Donate pools (argnums 2).
    For a config with experts the step also returns the grouped expert
    layers' counters (``models.EXPERT_STATS``, summed over the layers);
    rows whose qpos is -1 route to no expert."""
    if chunk % page_size:
        raise ValueError(f"chunk {chunk} must be a multiple of the page "
                         f"size {page_size}")
    rules, values, shard_tree = _plan_state(
        cfg, mesh, _PagedShape(chunk, slots, "prefill"), "prefill",
        budget_gb=budget_gb, force_2d=force_2d)
    pool_vals, pool_sh = _pool_state(cfg, mesh, rules, total_pages,
                                     page_size)
    b = slots
    toks = jax.ShapeDtypeStruct((b, chunk), jnp.int32)
    pt = jax.ShapeDtypeStruct((b, max_pages), jnp.int32)
    qpos = jax.ShapeDtypeStruct((b, chunk), jnp.int32)
    pos0 = jax.ShapeDtypeStruct((b,), jnp.int32)
    key_arg = jax.ShapeDtypeStruct((2,), jnp.uint32)

    stats = cfg.moe is not None

    def chunk_step(params, tokens, pools, pt, qpos, pos0, key):
        with _step_scope("step_chunk_prefill", rules, kernel_backend, mesh):
            out = forward_prefill_chunk(
                params, cfg, {"tokens": tokens}, pools, pt, qpos, pos0,
                **({"expert_stats": True} if stats else {}))
            logits, pools = out[:2]
            with jax.named_scope("sample"):
                tok1 = sample_token_guarded(
                    logits[:, -1, : cfg.vocab_size], key, temperature)
        return (tok1, pools) + out[2:]

    return StepPlan(
        name=f"chunk_prefill:{cfg.name}:b{slots}c{chunk}",
        step_fn=chunk_step,
        abstract_args=(values, toks, pool_vals, pt, qpos, pos0, key_arg),
        in_shardings=(shard_tree, None, pool_sh, None, None, None, None),
        out_shardings=(None, pool_sh) + ((None,) if stats else ()),
        rules=rules,
        donate_argnums=(2,),
        meta={"kind": "chunk_prefill", "chunk": chunk,
              "page_size": page_size, "total_pages": total_pages,
              "expert_stats": stats,
              "kernel_backend": _meta_backend(kernel_backend),
              "attention": _meta_attention(kernel_backend),
              "sharding": _meta_sharding(mesh, rules)},
    )


def build_paged_generate_plan(cfg, mesh, *, slots: int, gen: int,
                              total_pages: int, page_size: int,
                              max_pages: int, temperature: float = 0.0,
                              force_2d: bool | None = None,
                              budget_gb: float = 8.0,
                              kernel_backend: str | None = None) -> StepPlan:
    """``gen`` paged decode steps as one on-device scan (the paged
    analogue of :func:`build_generate_plan`; gen=1 is the single decode
    step the engine interleaves with prefill chunks).

    step_fn(params, tok0 (slots,), pools, pt (slots, max_pages),
    pos0 (slots,), key) -> (tokens (slots, gen), pools).  The page table is
    fixed across the burst — the scheduler pre-allocates every page the
    burst can write, so mid-burst writes never land on an unmapped page
    (unmapped entries point at the dummy page 0, whose reads are masked).
    Dead slots run with pt row 0 / pos 0 and their tokens are ignored.
    Donate pools (argnums 2).  For a config with experts the step takes
    ``live`` (slots,) int32 before the key, the sub-steps of the burst in
    which each row's token is kept (0 for a dead slot; rows past it route
    to no expert), and also returns the grouped expert layers' counters
    summed over the layers and sub-steps."""
    rules, values, shard_tree = _plan_state(
        cfg, mesh, _PagedShape(max_pages * page_size, slots, "decode"),
        "decode", budget_gb=budget_gb, force_2d=force_2d)
    pool_vals, pool_sh = _pool_state(cfg, mesh, rules, total_pages,
                                     page_size)
    b = slots
    tok0 = jax.ShapeDtypeStruct((b,), jnp.int32)
    pt = jax.ShapeDtypeStruct((b, max_pages), jnp.int32)
    pos0 = jax.ShapeDtypeStruct((b,), jnp.int32)
    key_arg = jax.ShapeDtypeStruct((2,), jnp.uint32)

    stats = cfg.moe is not None

    def generate_step(params, tok0, pools, pt, pos0, *rest):
        # expert configs take live (slots,) int32 before the key: the
        # sub-steps of the burst in which each row's token is kept
        live, key = rest if stats else (None, rest[0])
        with _step_scope(f"step_paged_generate_g{gen}", rules,
                         kernel_backend, mesh):
            def body(carry, j):
                tok, pools, pos, key, st = carry
                if stats:
                    logits, pools, counts = forward_decode_paged(
                        params, cfg, {"tokens": tok}, pools, pt, pos,
                        j < live, expert_stats=True)
                    st = st + counts
                else:
                    logits, pools = forward_decode_paged(
                        params, cfg, {"tokens": tok}, pools, pt, pos)
                key, sub = jax.random.split(key)
                with jax.named_scope("sample"):
                    nxt = sample_token_guarded(
                        logits[:, -1, : cfg.vocab_size], sub, temperature)
                # a quarantined (-1) row keeps scanning on token 0 so its
                # embedding lookup stays in range; the emitted -1 persists
                # (its KV history is poisoned, logits stay non-finite) and
                # the engine truncates at the first marker
                return (jnp.maximum(nxt, 0), pools, pos + 1, key, st), nxt

            st0 = (jnp.zeros((len(EXPERT_STATS),), jnp.int32) if stats
                   else None)
            steps = jnp.arange(gen, dtype=jnp.int32) if stats else None
            (_, pools, _, _, st), toks = jax.lax.scan(
                body, (tok0, pools, pos0, key, st0), steps, length=gen)
        out = (jnp.moveaxis(toks, 0, 1), pools)  # (slots, gen)
        return out + (st,) if stats else out

    live_arg = (jax.ShapeDtypeStruct((b,), jnp.int32),) if stats else ()
    return StepPlan(
        name=f"paged_generate:{cfg.name}:b{slots}g{gen}",
        step_fn=generate_step,
        abstract_args=(values, tok0, pool_vals, pt, pos0) + live_arg
        + (key_arg,),
        in_shardings=(shard_tree, None, pool_sh, None, None)
        + (None,) * len(live_arg) + (None,),
        out_shardings=(None, pool_sh) + ((None,) if stats else ()),
        rules=rules,
        donate_argnums=(2,),
        meta={"kind": "paged_generate", "gen": gen,
              "page_size": page_size, "total_pages": total_pages,
              "temperature": temperature, "expert_stats": stats,
              "kernel_backend": _meta_backend(kernel_backend),
              "attention": _meta_attention(kernel_backend),
              "sharding": _meta_sharding(mesh, rules)},
    )
