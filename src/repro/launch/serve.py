"""Serving driver: batched prefill + on-device decode with a quantized
(LoRDS) model.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --batch 4 --prompt-len 64 --gen 32

Request flow: a batch of prompts is prefilled once (cache build), then the
whole generation budget runs as a *single jitted on-device loop*
(``jax.lax.scan`` over decode steps, donated cache) — one host dispatch for
all generated tokens, so decode cost is the fused kernels, not Python
round-trips.  The model runs fully quantized (packed Q + B·A scales), the
M<=8 matmuls hit the weight-stationary decode GEMV kernel, and with
``--kv-cache int8`` the KV cache is stored as per-head int8 + f32 scales
(~2x less cache HBM traffic per token at capacity).

``loop='host'`` keeps the legacy per-token Python loop as the parity
oracle: token-for-token identical output is asserted in the test suite.
"""
from __future__ import annotations

import argparse
import os
import time

import jax

if jax.default_backend() == "cpu":
    os.environ.setdefault("REPRO_CPU_EXEC", "1")
import jax.numpy as jnp
import numpy as np

from repro.configs import ShapeCfg, get_config, smoke_variant
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_generate_plan, build_plan, sample_token
from repro.models import cache_init, model_init, split_tree


def serve_batch(cfg, *, batch: int, prompt_len: int, gen: int,
                mesh=None, seed: int = 0, params=None, prompts=None,
                kernel_backend: str | None = None, loop: str = "scan",
                temperature: float = 0.0,
                kv_cache: str | None = None) -> dict:
    """``kernel_backend`` selects the quantized-matmul path (pallas /
    interpret / ref / dense); None = platform default via the dispatch
    layer.  ``loop`` picks the decode driver: 'scan' (default — single
    jitted on-device generation loop) or 'host' (legacy per-token Python
    loop, the parity oracle).  ``kv_cache`` overrides
    ``cfg.kv_cache_dtype`` ('bf16' | 'int8').  A multi-device ``mesh`` runs
    the whole pipeline sharded: params and KV cache are placed onto the
    plan's NamedShardings, and the fused qmatmuls execute tensor-parallel
    over the mesh's 'model' axis inside the jitted steps.  (For repeated
    min-timed decode measurements use
    ``benchmarks.bench_serve.paired_decode_tok_s``, which interleaves both
    KV formats' compiled loops.)"""
    if loop not in ("scan", "host"):
        raise ValueError(f"unknown decode loop {loop!r}")
    if kv_cache is not None:
        cfg = cfg.with_(kv_cache_dtype=kv_cache)
    if loop == "host" and temperature > 0.0:
        raise ValueError("temperature sampling needs the on-device loop")
    mesh = mesh or make_host_mesh()
    capacity = prompt_len + gen
    prefill_shape = ShapeCfg("serve_prefill", capacity, batch, "prefill")
    decode_shape = ShapeCfg("serve_decode", capacity, batch, "decode")

    key = jax.random.PRNGKey(seed)
    if params is None:
        params, _ = split_tree(model_init(key, cfg))
    cache, _ = split_tree(cache_init(cfg, batch, capacity))

    pre_plan = build_plan(cfg, mesh, prefill_shape,
                          kernel_backend=kernel_backend)
    if np.prod(tuple(mesh.shape.values())) > 1:
        # commit params/cache to the plan layout up front (codes + B rows
        # sharded over 'model', factors replicated, cache per act rules) so
        # prefill/decode jits run sharded instead of resharding per call
        params = jax.device_put(params, pre_plan.in_shardings[0])
        cache = jax.device_put(cache, pre_plan.in_shardings[2])

    if prompts is None:
        prompts = np.random.default_rng(seed).integers(
            0, cfg.vocab_size, (batch, capacity)).astype(np.int32)
    else:
        pad = np.zeros((batch, capacity - prompts.shape[1]), np.int32)
        prompts = np.concatenate([prompts, pad], axis=1).astype(np.int32)

    with mesh:
        prefill = jax.jit(pre_plan.step_fn, donate_argnums=(2,))

        t0 = time.time()
        # dead-padding prefill: only the first prompt_len columns are live
        # (-1 positions mask the rest out of attention and the last-token
        # logits come from column prompt_len-1, not the padded window end)
        positions = jnp.arange(capacity, dtype=jnp.int32)[None]
        positions = jnp.broadcast_to(
            jnp.where(positions < prompt_len, positions, -1),
            (batch, capacity))
        if cfg.input_kind == "tokens":
            batch_in = {"tokens": jnp.asarray(prompts),
                        "positions": positions}
            step_embeds = None
        else:
            batch_in = {"embeds": jax.random.normal(
                key, (batch, capacity, cfg.d_model), jnp.bfloat16),
                "positions": positions}
            # the per-step frontend is stubbed: every decode step feeds the
            # same embedding (matching the legacy loop, which reused `key`)
            step_embeds = jax.random.normal(
                key, (batch, 1, cfg.d_model), jnp.bfloat16)
        logits, cache = prefill(params, batch_in, cache)
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0

        # first generated token: sampled under the same policy as the loop
        # (greedy at temperature 0) so position 0 isn't frozen to argmax
        key0, gen_key = jax.random.split(jax.random.PRNGKey(seed + 1))
        tok = sample_token(logits[:, -1, : cfg.vocab_size], key0, temperature)

        if loop == "scan":
            if gen > 1:
                gen_plan = build_generate_plan(
                    cfg, mesh, decode_shape, gen=gen - 1,
                    temperature=temperature, kernel_backend=kernel_backend)
                pos0 = jnp.full((batch,), prompt_len, jnp.int32)
                # AOT-compile outside the timed region (lower() neither
                # executes nor consumes the donated cache), so decode_tok_s
                # measures the on-device loop, not tracing + compilation
                generate = jax.jit(
                    gen_plan.step_fn, donate_argnums=(2,)
                ).lower(params, tok, cache, pos0, gen_key,
                        step_embeds).compile()
                t0 = time.time()
                toks, cache = generate(params, tok, cache, pos0, gen_key,
                                       step_embeds)
                jax.block_until_ready(toks)
                t_decode = time.time() - t0
                toks = np.concatenate(
                    [np.asarray(tok)[:, None], np.asarray(toks)], axis=1)
            else:
                toks = np.asarray(tok)[:, None]
                t_decode = 0.0
        else:  # legacy per-token host loop (parity oracle)
            dec_plan = build_plan(cfg, mesh, decode_shape,
                                  kernel_backend=kernel_backend)
            decode = jax.jit(dec_plan.step_fn, donate_argnums=(2,))
            generated = [np.asarray(tok)]
            t0 = time.time()
            for i in range(gen - 1):
                pos = jnp.full((batch,), prompt_len + i, jnp.int32)
                if cfg.input_kind == "tokens":
                    step_in = {"tokens": tok}
                else:
                    step_in = {"embeds": step_embeds}
                logits, cache = decode(params, step_in, cache, pos)
                tok = jnp.argmax(
                    logits[:, -1, : cfg.vocab_size], axis=-1).astype(jnp.int32)
                generated.append(np.asarray(tok))
            jax.block_until_ready(tok)
            t_decode = time.time() - t0
            toks = np.stack(generated, axis=1)

    return {
        "tokens": toks,
        "prefill_tok_s": batch * prompt_len / max(t_prefill, 1e-9),
        "prefill_ms": t_prefill * 1e3,
        "decode_tok_s": (batch * (gen - 1) / max(t_decode, 1e-9)
                         if gen > 1 else 0.0),
        "decode_ms": t_decode * 1e3,
        "decode_loop": loop,
        "kv_cache_dtype": cfg.kv_cache_dtype,
        "kernel_backend": pre_plan.meta["kernel_backend"],
        "attention": pre_plan.meta["attention"],
    }


def serve_engine(cfg, *, n_requests: int = 8, mesh=None, seed: int = 0,
                 slots: int = 4, total_pages: int = 48, page_size: int = 8,
                 max_pages: int = 12, chunk: int = 16, burst: int = 4,
                 kernel_backend: str | None = None,
                 deadline_s: float | None = None,
                 admission_budget: int | None = None,
                 faults=None, timeout_s: float = 300.0) -> dict:
    """Drive the continuous-batching :class:`repro.launch.engine.Engine`
    over a seeded synthetic ragged trace (the CLI's ``--engine N`` mode).

    ``deadline_s`` attaches a per-request latency budget, and
    ``admission_budget`` bounds the queue (overload shedding); ``faults``
    takes a :class:`repro.robustness.FaultPlan` for chaos runs.  Returns
    ``Engine.run``'s stats dict — every request ends in exactly one
    terminal status even under injected faults.
    """
    from repro.launch.engine import Engine, Request

    rng = np.random.default_rng(seed)
    cap_tokens = min(max_pages, total_pages - 1) * page_size
    reqs = []
    t = 0.0
    for rid in range(n_requests):
        plen = int(rng.integers(4, max(chunk, 8) + 1))
        gen = int(rng.integers(4, max(cap_tokens - chunk, 8) + 1))
        gen = min(gen, cap_tokens - (-(-plen // chunk) * chunk) + 1, 24)
        prompt = rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32)
        reqs.append(Request(rid, prompt, max(gen, 1), arrival=t,
                            deadline_s=deadline_s))
        t += float(rng.exponential(0.01))
    eng = Engine(cfg, slots=slots, total_pages=total_pages,
                 page_size=page_size, max_pages=max_pages, chunk=chunk,
                 burst=burst, mesh=mesh, kernel_backend=kernel_backend,
                 params=None, seed=seed, faults=faults,
                 admission_budget=admission_budget)
    return eng.run(reqs, timeout_s=timeout_s)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--engine", type=int, default=None, metavar="N",
                    help="serve N synthetic ragged requests through the "
                         "continuous-batching paged engine instead of one "
                         "fixed batch")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline for --engine mode")
    ap.add_argument("--admission-budget", type=int, default=None,
                    help="max queued requests before shedding (--engine)")
    ap.add_argument("--loop", default="scan", choices=["scan", "host"],
                    help="decode driver: single jitted on-device scan "
                         "(default) or the legacy per-token host loop")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 = temperature sampling (scan loop)")
    ap.add_argument("--kv-cache", default=None, choices=["bf16", "int8"],
                    help="KV-cache storage (default: cfg.kv_cache_dtype)")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["pallas", "interpret", "ref", "dense"],
                    help="quantized-matmul dispatch backend "
                         "(default: fused pallas on TPU, ref elsewhere)")
    ap.add_argument("--codebook", default=None,
                    choices=["nf4", "nf3", "nf2", "int8", "int4", "fp4"],
                    help="override the weight codebook (nf3 = the true "
                         "3-bit serving config: 8 codes packed into 3 "
                         "bytes, unpacked in-kernel)")
    ap.add_argument("--scale-dtype", default=None, choices=["f32", "bf16"],
                    help="storage dtype of the LoRDS B/A factors (default: "
                         "config; sub-4-bit codebooks default to bf16 so "
                         "total storage stays under 0.5 bytes/weight)")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="host mesh shape, e.g. 2x4 (needs that many visible "
                         "devices; on CPU force them via XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    if args.codebook or args.scale_dtype:
        from repro.core import lut

        q = cfg.quant
        if args.codebook:
            q = q.with_(codebook=args.codebook)
        if args.scale_dtype:
            q = q.with_(scale_dtype={"f32": jnp.float32,
                                     "bf16": jnp.bfloat16}[args.scale_dtype])
        elif lut.codebook_bits(q.codebook) < 4:
            # sub-4-bit point of the storage Pareto: bf16 factors keep the
            # B/A overhead below the packing win (nf3 ≈ 0.39 bytes/weight
            # incl. scales vs 0.41 with f32 factors)
            q = q.with_(scale_dtype=jnp.bfloat16)
        cfg = cfg.with_(quant=q)
    mesh = None
    if args.mesh:
        data, model = (int(v) for v in args.mesh.lower().split("x"))
        mesh = make_host_mesh(data=data, model=model)
    if args.engine is not None:
        stats = serve_engine(cfg, n_requests=args.engine, mesh=mesh,
                             kernel_backend=args.kernel_backend,
                             deadline_s=args.deadline_s,
                             admission_budget=args.admission_budget)
        print(f"[serve] engine: {stats['statuses']} "
              f"goodput {stats['goodput_tok_s']:.1f} tok/s "
              f"p50 {stats['latency_p50_s'] * 1e3:.0f}ms "
              f"p99 {stats['latency_p99_s'] * 1e3:.0f}ms "
              f"evictions {stats['evictions']} shed {stats['shed']} "
              f"page_audit_ok {stats['page_audit']['ok']}")
        n_failed = stats["statuses"].get("failed", 0)
        if n_failed:
            raise SystemExit(f"[serve] {n_failed} request(s) failed; step "
                             f"errors: {stats['step_errors'][-3:]}")
        return
    out = serve_batch(cfg, batch=args.batch, prompt_len=args.prompt_len,
                      gen=args.gen, mesh=mesh,
                      kernel_backend=args.kernel_backend,
                      loop=args.loop, temperature=args.temperature,
                      kv_cache=args.kv_cache)
    print(f"[serve] backend={out['kernel_backend']} loop={out['decode_loop']} "
          f"kv={out['kv_cache_dtype']} attention={out['attention']} "
          f"prefill {out['prefill_tok_s']:.1f} tok/s, "
          f"decode {out['decode_tok_s']:.1f} tok/s")
    print("[serve] sample tokens:", out["tokens"][0][:16])


if __name__ == "__main__":
    main()
