"""Bring-up check on a TPU: llama3-8b at full width through the main path.

    python chip_smoke.py             # one chip: init, serve, parity, PEFT
    python chip_smoke.py --chips 4   # 1x4 model-parallel engine vs unsharded

One process drives every phase on the real device, through the entry
points a user calls (``model_init``, the paged ``Engine``, ``build_plan``):

  start-up  the device is a TPU; no environment override can steer a step
            into interpret mode, CPU-tuned tiles or the CPU upcast path;
            the kernel backend is pinned to ``pallas``
  init      llama3-8b (32 layers, d_model 4096, d_ff 14336, vocab 128256),
            nf4 LoRDS at the parity rank of block 128, weights drawn from
            ``--seed``
  serve     ~8 seeded requests of mixed prompt lengths (up to 512 tokens,
            32 new tokens each) through the engine: int8 paged KV, chunked
            prefill, burst decode; every request must end ``completed``,
            and each of its tokens must be a greedy pick of the fused path
            fed the same history (prefill, then decode steps)
  parity    prefill logits of one prompt, fused kernels vs the ``ref``
            backend on the same params
  peft      3 LoRDS-PEFT steps (fused forward and backward); finite losses

With ``--chips 4`` only the model-parallel phase runs: a prefill and a few
engine decode steps on a 1x4 ``model`` mesh, each compared with the
unsharded fused path on device 0.  Timings and counters are printed as they come;
the last line of stdout is one JSON object naming the device.  Any failed
check exits non-zero before that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
BACKEND = "pallas"
# Environment switches that would move a step off the fused chip path:
# interpret-mode kernels, a forced backend, a tile table tuned elsewhere, or
# the CPU operand-upcast branch of f32_einsum.
STEERING_ENV = ("REPRO_INTERPRET_KERNELS", "REPRO_KERNEL_BACKEND",
                "REPRO_AUTOTUNE_CACHE", "REPRO_CPU_EXEC")
# Logit parity, fused vs ref, at full width: the CPU model-level tests hold
# one fused mixer to cosine > 0.999 against the ref oracle
# (tests/test_attn_fastpath.py) and bf16 kernel outputs to rtol = atol =
# 2e-2 (tests/test_serve_decode.py).  32 stacked layers compound the bf16
# rounding of weight tiles and activations, and XLA runs the ref path's f32
# scale product S = B·A at its default (bf16-pass) matmul precision on the
# TPU, so the largest logit error may reach 2.5x that rtol of the largest
# logit; the direction of the logit vector must still agree to 0.999.
COS_MIN = 0.999
REL_ERR_MAX = 0.05
# The smoke's traffic: requests of mixed prompt lengths up to MAX_PROMPT,
# NEW_TOKENS each, over SLOTS engine slots; one PARITY_LEN-token prefill;
# PEFT steps at PEFT_SEQ tokens, batch 1.
REQUESTS, SLOTS, MAX_PROMPT, NEW_TOKENS = 8, 8, 512, 32
PARITY_LEN, PEFT_SEQ, PEFT_STEPS = 256, 256, 3


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def startup(n_chips: int):
    steering = [v for v in STEERING_ENV if os.environ.get(v)]
    check(not steering, f"refusing to start with {steering} set: each can "
                        "move a step off the fused chip path")
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX found {devs[0].platform} devices")
    check(len(devs) >= n_chips, f"need {n_chips} chips, found {len(devs)}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{enable_compile_cache()}")
    return devs


def model_cfg():
    """llama3-8b as published (all 32 layers), int8 KV, peft mode."""
    from repro.configs import get_config

    cfg = get_config("llama3-8b")
    return cfg.with_(kv_cache_dtype="int8",
                     quant=cfg.quant.with_(mode="peft"))


def memory(dev) -> str:
    """Live and peak device bytes as the device reports them."""
    stats = dev.memory_stats() or {}
    return (f"bytes_in_use {stats.get('bytes_in_use')}, peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use')}")


def init_params(cfg, seed: int, dev):
    import jax

    from repro.models import model_init, split_tree

    t0 = time.perf_counter()
    params, _ = split_tree(model_init(jax.random.PRNGKey(seed), cfg))
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"init {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"d_ff={cfg.d_ff} codebook={cfg.quant.codebook} "
        f"block={cfg.quant.block_size}: {dt:.1f} s, params {nbytes} bytes, "
        f"{memory(dev)}")
    return params


def check_plan_meta(meta: dict, what: str) -> None:
    check(meta["kernel_backend"] == BACKEND,
          f"{what}: kernel_backend {meta['kernel_backend']!r}")
    check(meta["attention"] == "fused",
          f"{what}: attention {meta['attention']!r}")


def make_requests(cfg, n: int, max_prompt: int, new_tokens: int, seed: int):
    import numpy as np

    from repro.launch.engine import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(16, max_prompt + 1, n)
    lens[0] = max_prompt
    return [Request(i, rng.integers(0, cfg.vocab_size, int(plen))
                    .astype(np.int32), new_tokens)
            for i, plen in enumerate(lens)]


def serve(cfg, params, reqs, *, slots: int, mesh=None, seed: int = 0,
          label: str = "serve"):
    from repro.launch.engine import Engine

    page, chunk = 16, 128
    # a request's peak footprint: its prompt rounded up to whole chunks, or
    # its last decode write, whichever is further
    longest = max(max(-(-len(r.tokens) // chunk) * chunk,
                      len(r.tokens) + r.max_new - 1) for r in reqs)
    max_pages = -(-longest // page)
    eng = Engine(cfg, slots=slots, total_pages=slots * max_pages + 1,
                 page_size=page, max_pages=max_pages, chunk=chunk, burst=8,
                 mesh=mesh, kernel_backend=BACKEND, params=params, seed=seed)
    check_plan_meta(eng.chunk_plan.meta, f"{label} chunk plan")
    check_plan_meta(eng.burst_plan.meta, f"{label} decode plan")
    t0 = time.perf_counter()
    eng.warmup()
    warm = eng.compile_counts()
    log(f"{label}: engine steps compiled in {time.perf_counter() - t0:.1f} s "
        f"(slots {slots}, page {page}, chunk {chunk}, burst 8, kv "
        f"{cfg.kv_cache_dtype}); executables {warm}")
    stats = eng.run(reqs, timeout_s=600.0)
    check(eng.compile_counts() == warm,
          f"{label}: the run compiled again after warmup: "
          f"{warm} -> {eng.compile_counts()}")
    log(f"{label}: statuses {stats['statuses']} in {stats['wall_s']:.2f} s; "
        f"generated {stats['generated_tokens']} tokens, chunk steps "
        f"{stats['chunk_steps']}, decode steps {stats['decode_steps']}, "
        f"prefill_ms {stats['prefill_ms']:.1f}, decode_ms "
        f"{stats['decode_ms']:.1f}, evictions {stats['evictions']}, "
        f"page_audit_ok {stats['page_audit']['ok']}")
    bad = {r["rid"]: (r["status"], r["reason"]) for r in stats["records"]
           if r["status"] != "completed"}
    check(not bad and stats["completed"] == len(reqs),
          f"{label}: requests not completed {bad}; step errors "
          f"{stats['step_errors'][-3:]}")
    check(stats["page_audit"]["ok"], f"{label}: page audit "
                                     f"{stats['page_audit']['issues']}")
    return {r["rid"]: r["tokens"] for r in stats["records"]}


def forced_logits(cfg, params, reqs, outs, *, mesh):
    """The fused path's logits at every generated position of ``reqs`` when
    it is fed each prompt and then the engine's own tokens: one dead-padded
    prefill and one decode step per token on a contiguous KV cache.
    Returns (requests, new tokens, vocab) float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ShapeCfg
    from repro.launch.steps import build_plan
    from repro.models import cache_init, split_tree

    b, n = len(reqs), reqs[0].max_new
    plen = np.array([len(r.tokens) for r in reqs], np.int32)
    cap = int(plen.max()) + n
    tokens = np.zeros((b, cap), np.int32)
    for i, r in enumerate(reqs):
        tokens[i, : plen[i]] = r.tokens
    col = np.arange(cap, dtype=np.int32)[None]
    batch = {"tokens": jnp.asarray(tokens),
             "positions": jnp.asarray(np.where(col < plen[:, None], col, -1))}
    pre, dec = (build_plan(cfg, mesh, ShapeCfg(f"forced_{kind}", cap, b, kind),
                           kernel_backend=BACKEND)
                for kind in ("prefill", "decode"))
    check_plan_meta(pre.meta, "teacher-forced prefill plan")
    check_plan_meta(dec.meta, "teacher-forced decode plan")
    cache, _ = split_tree(cache_init(cfg, b, cap))
    logits, cache = jax.jit(pre.step_fn, donate_argnums=(2,))(
        params, batch, cache)
    decode = jax.jit(dec.step_fn, donate_argnums=(2,))
    rows = [np.asarray(logits[:, -1, : cfg.vocab_size], np.float32)]
    for j in range(n - 1):
        tok = jnp.asarray([outs[r.rid][j] for r in reqs], jnp.int32)
        logits, cache = decode(params, {"tokens": tok}, cache,
                               jnp.asarray(plen + j))
        rows.append(np.asarray(logits[:, -1, : cfg.vocab_size], np.float32))
    return np.stack(rows, axis=1)


def check_greedy(cfg, params, reqs, outs, *, mesh, label: str) -> None:
    """Every engine token must be a greedy pick of the fused path on
    ``mesh`` fed the same history: its logit within REL_ERR_MAX · max|logit|
    of that position's largest.  An exact argmax match is not asked for:
    random weights give near-flat logits whose top two can swap within the
    parity error, and one swap changes every later token."""
    import numpy as np

    short = {r.rid: len(outs[r.rid]) for r in reqs
             if len(outs[r.rid]) != r.max_new}
    check(not short, f"{label}: requests stopped short {short}")
    t0 = time.perf_counter()
    ref = forced_logits(cfg, params, reqs, outs, mesh=mesh)
    gen = np.array([outs[r.rid] for r in reqs])
    chosen = np.take_along_axis(ref, gen[..., None], axis=-1)[..., 0]
    slack = (ref.max(-1) - chosen) / (REL_ERR_MAX * np.abs(ref).max(-1))
    exact = int(np.sum(ref.argmax(-1) == gen))
    finite = bool(np.all(np.isfinite(ref)))
    log(f"{label}: {gen.size} engine tokens against the teacher-forced fused "
        f"path on mesh {dict(mesh.shape)}: {exact} are its argmax, worst "
        f"logit gap {float(slack.max()):.3f} of the bound, finite {finite}, "
        f"{time.perf_counter() - t0:.1f} s incl. compile")
    check(finite and float(slack.max()) <= 1.0,
          f"{label}: engine tokens are not greedy picks of the fused path")


def prefill_logits(cfg, params, tokens, *, backend: str, mesh):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ShapeCfg
    from repro.launch.steps import build_plan
    from repro.models import cache_init, split_tree

    b, s = tokens.shape
    plan = build_plan(cfg, mesh, ShapeCfg("parity", s, b, "prefill"),
                      kernel_backend=backend)
    if backend == BACKEND:
        check_plan_meta(plan.meta, f"prefill plan on mesh {dict(mesh.shape)}")
    if mesh.devices.size > 1:
        params = jax.device_put(params, plan.in_shardings[0])
    cache, _ = split_tree(cache_init(cfg, b, s))
    step = jax.jit(plan.step_fn, donate_argnums=(2,))
    t0 = time.perf_counter()
    logits, _ = step(params, {"tokens": jnp.asarray(tokens)}, cache)
    out = np.asarray(logits, np.float32)[..., : cfg.vocab_size]
    log(f"prefill {backend} on mesh {dict(mesh.shape)}: {s} tokens, "
        f"{time.perf_counter() - t0:.1f} s incl. compile")
    return out


def compare(got, want, what: str) -> None:
    import numpy as np

    g, w = got.ravel().astype(np.float64), want.ravel().astype(np.float64)
    finite = bool(np.all(np.isfinite(g)) and np.all(np.isfinite(w)))
    cos = float(g @ w / (np.linalg.norm(g) * np.linalg.norm(w) + 1e-30))
    rel = float(np.max(np.abs(g - w)) / (np.max(np.abs(w)) + 1e-30))
    log(f"parity {what}: cosine {cos:.6f} (min {COS_MIN}), max-abs error / "
        f"max|ref| {rel:.3e} (max {REL_ERR_MAX}), finite {finite}")
    check(finite and cos >= COS_MIN and rel <= REL_ERR_MAX,
          f"parity {what} outside thresholds")


def peft_steps(cfg, params, *, seq: int, batch: int, steps: int, seed: int,
               mesh):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ShapeCfg
    from repro.core import peft
    from repro.data import SyntheticLM
    from repro.launch.steps import build_plan
    from repro.optim import adamw_init

    plan = build_plan(cfg, mesh, ShapeCfg("peft", seq, batch, "train"),
                      kernel_backend=BACKEND)
    check(plan.meta["kernel_backend"] == BACKEND and plan.meta["mode"]
          == "peft", f"peft plan meta {plan.meta}")
    trainable, frozen = peft.partition(params, cfg.quant)
    # committed to the plan layout, so step 1 reuses step 0's executable
    trainable, frozen, opt = jax.device_put(
        (trainable, frozen, adamw_init(trainable)), plan.in_shardings[:3])
    step = jax.jit(plan.step_fn, in_shardings=plan.in_shardings,
                   out_shardings=plan.out_shardings,
                   donate_argnums=plan.donate_argnums)
    source = SyntheticLM(cfg.vocab_size, seq, batch, seed=seed)
    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        batch_in = {k: jnp.asarray(v)
                    for k, v in source.batch_at(i).items()}
        trainable, opt, metrics = step(trainable, frozen, opt, batch_in)
        losses.append(float(metrics["loss"]))
        log(f"peft step {i}: loss {losses[-1]:.6f} grad_norm "
            f"{float(metrics['grad_norm']):.6f} "
            f"{time.perf_counter() - t0:.1f} s")
    check(all(np.isfinite(losses)), f"non-finite PEFT losses {losses}")
    return losses


def one_chip(cfg, seed: int, devs) -> None:
    import numpy as np

    from repro.launch.mesh import make_host_mesh

    params = init_params(cfg, seed, devs[0])
    mesh = make_host_mesh()
    reqs = make_requests(cfg, REQUESTS, MAX_PROMPT, NEW_TOKENS, seed)
    log(f"serve: prompt lengths {[len(r.tokens) for r in reqs]}, "
        f"{NEW_TOKENS} new tokens each")
    outs = serve(cfg, params, reqs, slots=SLOTS, mesh=mesh, seed=seed)
    check_greedy(cfg, params, reqs, outs, mesh=mesh, label="serve")

    prompt = np.random.default_rng(seed + 1).integers(
        0, cfg.vocab_size, (1, PARITY_LEN)).astype(np.int32)
    fused = prefill_logits(cfg, params, prompt, backend=BACKEND, mesh=mesh)
    ref = prefill_logits(cfg, params, prompt, backend="ref", mesh=mesh)
    compare(fused, ref, f"fused vs ref prefill logits ({PARITY_LEN} tokens)")

    losses = peft_steps(cfg, params, seq=PEFT_SEQ, batch=1, steps=PEFT_STEPS,
                        seed=seed, mesh=mesh)
    log(f"peft losses {losses}; {memory(devs[0])}")


def four_chips(cfg, seed: int, devs) -> None:
    import numpy as np

    from repro.launch.mesh import make_host_mesh

    params = init_params(cfg, seed, devs[0])
    mesh1, mesh4 = make_host_mesh(), make_host_mesh(data=1, model=4)
    prompt = np.random.default_rng(seed + 1).integers(
        0, cfg.vocab_size, (1, PARITY_LEN)).astype(np.int32)
    compare(prefill_logits(cfg, params, prompt, backend=BACKEND, mesh=mesh4),
            prefill_logits(cfg, params, prompt, backend=BACKEND, mesh=mesh1),
            "1x4 model-parallel vs unsharded fused prefill logits")
    reqs = make_requests(cfg, 4, MAX_PROMPT, 4, seed)
    sharded = serve(cfg, params, reqs, slots=4, mesh=mesh4, seed=seed,
                    label="serve 1x4")
    check_greedy(cfg, params, reqs, sharded, mesh=mesh1, label="serve 1x4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, prompts and PEFT data")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        devs = startup(args.chips)
        (four_chips if args.chips == 4 else one_chip)(model_cfg(), args.seed,
                                                      devs)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    import jax

    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
