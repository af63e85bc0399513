"""Paged KV serving: page-pool attention kernels vs the gather oracles,
chunked-prefill/paged-decode model parity, the continuous-batching engine
token-for-token against the PR 2 scan loop (ragged prompts, int8 + bf16,
GQA + MLA, slot reuse, forced eviction + recompute), the jaxpr guard that
the paged int8 decode step never gathers the pool into a contiguous
temporary or dequantizes it outside a kernel launch, and sharded-vs-single
engine parity under the 8-device harness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multidevice_compat import multidevice, single_mesh, tp_mesh
from repro.configs import get_config, smoke_variant
from repro.kernels import dispatch
from repro.kernels.dispatch import qattention
from repro.launch.engine import Engine, Request
from repro.launch.serve import serve_batch
from repro.models import (
    forward_decode,
    forward_decode_paged,
    forward_prefill,
    forward_prefill_chunk,
    model_init,
    paged_cache_init,
    split_tree,
)


def _cos(a, b):
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(a @ b / max(np.linalg.norm(a) * np.linalg.norm(b), 1e-30))


def _maxerr(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def _smoke(arch, kv):
    return smoke_variant(get_config(arch)).with_(num_layers=2,
                                                 kv_cache_dtype=kv)


def _prompts(cfg, plens, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32)
            for p in plens]


def _scan_tokens(cfg, prompt, gen, params):
    """Per-request reference: the PR 2 single-sequence scan loop."""
    out = serve_batch(cfg, batch=1, prompt_len=len(prompt), gen=gen,
                      params=params, prompts=prompt[None],
                      kernel_backend="interpret", loop="scan")
    return list(out["tokens"][0])


# ---------------------------------------------------------------------------
# paged decode kernels: fused (page-table scalar prefetch) vs gather oracle
# ---------------------------------------------------------------------------

# (batch, page_size, logical pages, physical pages, nh, nkv, hd) — positions
# off the page grid, GQA group > 1, pool larger than any one sequence
PAGED_SHAPES = [(2, 8, 5, 9, 4, 2, 16), (1, 16, 3, 7, 8, 2, 24),
                (2, 16, 20, 41, 4, 2, 16), (2, 8, 21, 44, 8, 2, 16),
                (3, 16, 20, 61, 4, 2, 32), (32, 16, 144, 1793, 32, 8, 128)]
# positions of the cases of the live-context walk (blocks of 128 tokens:
# 8 pages at page size 16, 16 at page size 8); the others are drawn
LIVE_POS = {
    # a slot at pos 0, and one live page of 20
    (2, 16, 20, 41, 4, 2, 16): [0, 15],
    # 21 pages, not a multiple of 16: the last block is 5 pages, the first
    # slot's window ends in it, the second slot's context ends in block 0
    (2, 8, 21, 44, 8, 2, 16): [167, 100],
    # lengths on block boundaries (128, 256) and one token past one
    (3, 16, 20, 61, 4, 2, 32): [127, 255, 128],
    # the batch-decode cell's geometry (Qwen3-8B heads, 32 slots, windows
    # of 144 pages of 16, a 1,793-page pool) with its 180-400 token
    # contexts: one long chain of blocks across every slot
    (32, 16, 144, 1793, 32, 8, 128):
        np.random.default_rng(5).integers(179, 400, 32).tolist(),
}


def _page_table(rng, b, np_, total):
    """Distinct physical pages per row, non-contiguous and unordered."""
    rows = [rng.choice(np.arange(1, total), size=np_, replace=False)
            for _ in range(b)]
    return jnp.asarray(np.stack(rows), jnp.int32)


@pytest.mark.parametrize("b,ps,np_,tp,nh,nkv,hd", PAGED_SHAPES)
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_kernel_matches_ref(b, ps, np_, tp, nh, nkv, hd, kv):
    rng = np.random.default_rng(0)
    q = jax.random.normal(jax.random.PRNGKey(0), (b, nh, hd))
    pt = _page_table(rng, b, np_, tp)
    pos = LIVE_POS.get((b, ps, np_, tp, nh, nkv, hd))
    if pos is None:
        pos = rng.integers(1, np_ * ps, (b,))
    pos = jnp.asarray(pos, jnp.int32)
    sc = 1.0 / hd ** 0.5
    if kv == "int8":
        kp = jnp.asarray(rng.integers(-127, 128, (tp, ps, nkv, hd)), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (tp, ps, nkv, hd)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.05, (tp, ps, nkv)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.05, (tp, ps, nkv)), jnp.float32)
        args = (q, kp, vp, pt, pos, ks, vs)
    else:
        kp = jax.random.normal(jax.random.PRNGKey(1), (tp, ps, nkv, hd),
                               jnp.bfloat16)
        vp = jax.random.normal(jax.random.PRNGKey(2), (tp, ps, nkv, hd),
                               jnp.bfloat16)
        args = (q, kp, vp, pt, pos)
    y_ref = qattention("paged_decode", *args, logit_scale=sc, backend="ref")
    y_int = qattention("paged_decode", *args, logit_scale=sc,
                       backend="interpret")
    assert _cos(y_int, y_ref) > 0.9999
    assert _maxerr(y_int, y_ref) < 3e-5


@pytest.mark.parametrize("interpret", ["backend", "nan_scratch"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_kernel_skips_dead_pages(interpret, kv):
    """NaN in every pool position past each slot's length — the rest of
    its last page, every later page, and the dummy page 0 that its dead
    table entries point at — never reaches the output: dead pages are
    neither read into the sums nor computed on.  A bf16 pool holds the NaN
    in its codes, an int8 pool in its scales.  ``nan_scratch`` runs the
    kernel under the TPU interpreter, which fills fresh VMEM with NaN and
    raises on a read out of bounds: its dead table entries name a page
    past the pool, so a copy of any dead page fails, and a block that
    computed on pages it never copied would show."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.attn_decode import attn_decode_gqa_paged_pallas

    b, ps, np_, nh, nkv, hd = 3, 8, 40, 8, 2, 16
    pos = np.array([0, 150, 19])
    live = -(-(pos + 1) // ps)
    tp = int(live.sum()) + 4
    rng = np.random.default_rng(3)
    pages = rng.permutation(np.arange(1, tp))
    pt = np.zeros((b, np_), np.int32)
    start = np.concatenate([[0], np.cumsum(live)])
    for i in range(b):
        pt[i, : live[i]] = pages[start[i]: start[i + 1]]
    q = jax.random.normal(jax.random.PRNGKey(0), (b, nh, hd))
    if kv == "int8":
        kp = rng.integers(-127, 128, (tp, ps, nkv, hd)).astype(np.int8)
        vp = rng.integers(-127, 128, (tp, ps, nkv, hd)).astype(np.int8)
        ks = rng.uniform(0.01, 0.05, (tp, ps, nkv)).astype(np.float32)
        vs = rng.uniform(0.01, 0.05, (tp, ps, nkv)).astype(np.float32)
        pools = [kp, vp, ks, vs]
    else:
        pools = [np.array(jax.random.normal(jax.random.PRNGKey(i),
                                            (tp, ps, nkv, hd)), np.float32)
                 for i in (1, 2)]
    sc = 1.0 / hd ** 0.5
    pt, posj = jnp.asarray(pt), jnp.asarray(pos, jnp.int32)
    dtype = jnp.int8 if kv == "int8" else jnp.bfloat16

    def operands():
        return [jnp.asarray(x, dtype if x.ndim == 4 else jnp.float32)
                for x in pools]

    kv_ref = operands()
    y_ref = qattention("paged_decode", q, kv_ref[0], kv_ref[1], pt, posj,
                       *kv_ref[2:], logit_scale=sc, backend="ref")
    dead = np.ones((tp, ps), bool)
    for i in range(b):
        for t in range(pos[i] + 1):
            dead[pt[i, t // ps], t % ps] = False
    assert dead[0].all() and dead.sum() > (tp - live.sum()) * ps
    for x in pools:
        if x.dtype != np.int8:
            x[dead] = np.nan
    kp, vp, *scales = operands()
    if interpret == "backend":
        y = qattention("paged_decode", q, kp, vp, pt, posj, *scales,
                       logit_scale=sc, backend="interpret")
    else:
        qg = q.reshape(b, nkv, nh // nkv, hd)
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 8 - nh // nkv), (0, 0)))
        unmapped = jnp.arange(np_)[None, :] >= jnp.asarray(live)[:, None]
        y = attn_decode_gqa_paged_pallas(
            jnp.where(unmapped, tp + 5, pt), qg, kp, vp, posj + 1, *scales,
            logit_scale=sc,
            interpret=pltpu.InterpretParams())[:, :, : nh // nkv]
        y = y.reshape(b, nh, hd)
    assert np.isfinite(np.asarray(y)).all()
    assert _maxerr(y, y_ref) < 3e-5


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_mla_decode_kernel_matches_ref(kv):
    b, ps, np_, tp = 2, 8, 4, 7
    nh, lat, rope = 4, 32, 16
    rng = np.random.default_rng(1)
    q_lat = jax.random.normal(jax.random.PRNGKey(0), (b, nh, lat))
    q_rope = jax.random.normal(jax.random.PRNGKey(1), (b, nh, rope))
    krp = jax.random.normal(jax.random.PRNGKey(2), (tp, ps, rope),
                            jnp.bfloat16)
    pt = _page_table(rng, b, np_, tp)
    pos = jnp.asarray([np_ * ps - 3, 9], jnp.int32)
    sc = 1.0 / (lat + rope) ** 0.5
    if kv == "int8":
        cp = jnp.asarray(rng.integers(-127, 128, (tp, ps, lat)), jnp.int8)
        cs = jnp.asarray(rng.uniform(0.01, 0.05, (tp, ps)), jnp.float32)
        args = (q_lat, q_rope, cp, krp, pt, pos, cs)
    else:
        cp = jax.random.normal(jax.random.PRNGKey(3), (tp, ps, lat),
                               jnp.bfloat16)
        args = (q_lat, q_rope, cp, krp, pt, pos)
    y_ref = qattention("paged_mla_decode", *args, logit_scale=sc,
                       backend="ref")
    y_int = qattention("paged_mla_decode", *args, logit_scale=sc,
                       backend="interpret")
    assert _cos(y_int, y_ref) > 0.9999
    assert _maxerr(y_int, y_ref) < 3e-5


def test_chunk_prefill_kernel_matches_ref():
    """Chunk queries attend gathered-window + raw-chunk KV with absolute
    positions; fused vs oracle on a ragged (dead-row) chunk."""
    b, cs, skv, nh, nkv, hd = 2, 8, 24, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, cs, nh, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, skv, nkv, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, skv, nkv, hd))
    # row 0: chunk positions 16..23 over a 24-token window; row 1: a short
    # final chunk (3 live tokens, rest dead) over a 19-token window
    qpos = np.full((b, cs), -1, np.int32)
    qpos[0] = np.arange(16, 24)
    qpos[1, :3] = np.arange(16, 19)
    kpos = np.full((b, skv), -1, np.int32)
    kpos[0] = np.arange(24)
    kpos[1, :19] = np.arange(19)
    qpos, kpos = jnp.asarray(qpos), jnp.asarray(kpos)
    sc = 1.0 / hd ** 0.5
    y_ref = qattention("chunk_prefill", q, k, v, qpos, kpos, logit_scale=sc,
                       backend="ref")
    y_int = qattention("chunk_prefill", q, k, v, qpos, kpos, logit_scale=sc,
                       backend="interpret")
    live = np.asarray(qpos) >= 0
    assert _cos(np.asarray(y_int)[live], np.asarray(y_ref)[live]) > 0.9999
    assert _maxerr(np.asarray(y_int)[live], np.asarray(y_ref)[live]) < 3e-5


# ---------------------------------------------------------------------------
# model layer: chunked paged prefill + paged decode vs the contiguous path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["llama3-8b", "minicpm3-4b"])
def test_paged_forward_matches_contiguous_logits(arch):
    """Single-chunk prefill keeps in-chunk KV raw (never reads it back
    through the pool), so paged logits are bitwise equal to the contiguous
    path even with an int8 pool — then every paged decode step must match
    the contiguous decode step exactly too.  Both paths run under the
    fused backend the serving plans pin (the ref oracle prefill is a
    different implementation with its own bf16 rounding)."""
    from repro.models import cache_init

    cfg = _smoke(arch, "int8")
    params, _ = split_tree(model_init(jax.random.PRNGKey(0), cfg))
    b, plen, ps, np_ = 2, 12, 8, 4
    cap = np_ * ps
    toks = jnp.asarray(np.stack(_prompts(cfg, [plen, plen])), jnp.int32)

    with dispatch.backend_scope("interpret"):
        cache, _ = split_tree(cache_init(cfg, b, cap))
        logits_c, cache = forward_prefill(params, cfg, {"tokens": toks},
                                          cache)

        pools, _ = split_tree(paged_cache_init(cfg, 2 * np_ + 1, ps))
        pt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
        pad = np.full((b, cap - plen), 0, np.int32)
        qpos = np.concatenate(
            [np.tile(np.arange(plen, dtype=np.int32), (b, 1)),
             np.full((b, cap - plen), -1, np.int32)], axis=1)
        logits_p, pools = forward_prefill_chunk(
            params, cfg,
            {"tokens": jnp.concatenate([toks, jnp.asarray(pad)], 1)},
            pools, pt, jnp.asarray(qpos), jnp.zeros((b,), jnp.int32))
        assert _maxerr(logits_p[:, 0], logits_c[:, 0]) == 0.0

        tok = jnp.argmax(logits_c[:, -1, : cfg.vocab_size],
                         -1).astype(jnp.int32)
        for step in range(3):
            pos = jnp.full((b,), plen + step, jnp.int32)
            lc, cache = forward_decode(params, cfg, {"tokens": tok}, cache,
                                       pos)
            lp, pools = forward_decode_paged(params, cfg, {"tokens": tok},
                                             pools, pt, pos)
            assert _maxerr(lp, lc) == 0.0, f"decode step {step}"
            tok = jnp.argmax(lc[:, -1, : cfg.vocab_size],
                             -1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# engine end-to-end: token-for-token vs the scan serve loop
# ---------------------------------------------------------------------------

ENGINE_COMBOS = [("llama3-8b", "bf16"), ("llama3-8b", "int8"),
                 ("minicpm3-4b", "bf16"), ("minicpm3-4b", "int8")]


@pytest.mark.parametrize("arch,kv", ENGINE_COMBOS)
def test_engine_matches_scan_serve(arch, kv):
    """Three ragged requests through two slots (forces slot reuse +
    admission queueing) produce exactly the tokens the fixed-capacity scan
    loop produces per request."""
    cfg = _smoke(arch, kv)
    params, _ = split_tree(model_init(jax.random.PRNGKey(0), cfg))
    prompts = _prompts(cfg, [10, 6, 13])
    gen = 5
    reqs = [Request(rid=i, tokens=p, max_new=gen, arrival=0.0)
            for i, p in enumerate(prompts)]
    eng = Engine(cfg, slots=2, total_pages=12, page_size=8, max_pages=4,
                 chunk=16, burst=4, kernel_backend="interpret",
                 params=params)
    stats = eng.run(reqs, timeout_s=600)
    assert stats["all_completed"], stats
    got = {r["rid"]: r["tokens"] for r in stats["records"]}
    for i, p in enumerate(prompts):
        assert got[i] == _scan_tokens(cfg, p, gen, params), f"rid={i}"


def test_engine_eviction_recompute_matches_scan():
    """A pool too small for the offered load forces the scheduler to evict
    the youngest sequence and recompute it from scratch later — tokens must
    still match the scan loop exactly, and the eviction path must actually
    have fired."""
    cfg = _smoke("llama3-8b", "int8")
    params, _ = split_tree(model_init(jax.random.PRNGKey(0), cfg))
    prompts = _prompts(cfg, [10, 9, 12], seed=11)
    gen = 12
    reqs = [Request(rid=i, tokens=p, max_new=gen, arrival=0.02 * i)
            for i, p in enumerate(prompts)]
    eng = Engine(cfg, slots=2, total_pages=5, page_size=8, max_pages=4,
                 chunk=16, burst=4, kernel_backend="interpret",
                 params=params)
    stats = eng.run(reqs, timeout_s=600)
    assert stats["all_completed"], stats
    assert stats["evictions"] > 0, "pool was sized to force eviction"
    got = {r["rid"]: r["tokens"] for r in stats["records"]}
    for i, p in enumerate(prompts):
        assert got[i] == _scan_tokens(cfg, p, gen, params), f"rid={i}"


def test_engine_multichunk_prefill_matches_scan():
    """Prompts longer than the chunk size run multiple interleaved prefill
    chunks (later chunks re-read earlier KV through the pool); with a bf16
    pool the stored window is exact, so tokens still match the scan loop."""
    cfg = _smoke("llama3-8b", "bf16")
    params, _ = split_tree(model_init(jax.random.PRNGKey(0), cfg))
    prompts = _prompts(cfg, [20, 11], seed=3)
    gen = 4
    reqs = [Request(rid=i, tokens=p, max_new=gen, arrival=0.0)
            for i, p in enumerate(prompts)]
    eng = Engine(cfg, slots=2, total_pages=12, page_size=8, max_pages=5,
                 chunk=8, burst=4, kernel_backend="interpret", params=params)
    stats = eng.run(reqs, timeout_s=600)
    assert stats["all_completed"], stats
    assert stats["chunk_steps"] >= 3        # 20-token prompt = 3 chunks of 8
    got = {r["rid"]: r["tokens"] for r in stats["records"]}
    for i, p in enumerate(prompts):
        assert got[i] == _scan_tokens(cfg, p, gen, params), f"rid={i}"


def test_engine_rejects_oversized_request():
    cfg = _smoke("llama3-8b", "int8")
    eng = Engine(cfg, slots=2, total_pages=6, page_size=8, max_pages=4,
                 chunk=16, burst=1, kernel_backend="interpret")
    big = Request(rid=0, tokens=np.zeros((40,), np.int32), max_new=8)
    with pytest.raises(ValueError, match="pages"):
        eng.run([big])


# ---------------------------------------------------------------------------
# jaxpr guard: the paged int8 decode step reads the pool in place — no
# contiguous-cache gather and no out-of-kernel pool dequant
# ---------------------------------------------------------------------------


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue  # tile-level internals live in VMEM, not HBM
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _walk_eqns(sub)


def _subjaxprs(val):
    if isinstance(val, jax.extend.core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _subjaxprs(v)


@pytest.mark.parametrize("arch", ["llama3-8b", "minicpm3-4b"])
def test_paged_decode_step_jaxpr_no_gather_or_dequant(arch):
    """The engine's jitted paged decode step must contain (a) no tensor of
    shape (slots, max_pages*page_size, ...) — the contiguous KV window the
    gather oracle materializes from the pool — and (b) no float tensor of a
    full int8 pool's shape outside kernel launches — an out-of-kernel pool
    dequant.  The ref plan must trip (a) or the guard is vacuous."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build_paged_generate_plan

    cfg = _smoke(arch, "int8")
    # slots/pages deliberately off every model dim of both smoke configs
    # (hd=16, d=64, qk=24, q_lora=32, ...): a (2, 40, ...) tensor can only
    # be a gathered contiguous KV window
    slots, ps, np_, total = 2, 8, 5, 11
    cap = np_ * ps
    mesh = make_host_mesh()

    def temporaries(backend):
        plan = build_paged_generate_plan(
            cfg, mesh, slots=slots, gen=1, total_pages=total, page_size=ps,
            max_pages=np_, kernel_backend=backend)
        pools = plan.abstract_args[2]
        pool_shapes = {tuple(l.shape[1:]) for l in jax.tree.leaves(pools)
                       if l.dtype == jnp.int8}
        jaxpr = jax.make_jaxpr(plan.step_fn)(*plan.abstract_args)
        bad = []
        for eqn in _walk_eqns(jaxpr.jaxpr):
            for v in eqn.outvars:
                aval = v.aval
                shape = tuple(getattr(aval, "shape", ()))
                if len(shape) < 3:
                    continue
                # (a) gathered contiguous window (any dtype: the int8
                # gather itself or its dequantized float twin)
                if shape[0] == slots and shape[1] == cap:
                    bad.append(("gather", eqn.primitive.name, shape,
                                str(aval.dtype)))
                # (b) full-pool dequant temporary (per stacked layer)
                if (jnp.issubdtype(aval.dtype, jnp.floating)
                        and (shape in pool_shapes
                             or shape[1:] in pool_shapes)):
                    bad.append(("dequant", eqn.primitive.name, shape,
                                str(aval.dtype)))
        return bad

    bad = temporaries("interpret")
    assert not bad, f"paged serving-path temporaries found: {bad}"

    # negative control: the gather oracle must trip the detector
    ref_bad = temporaries("ref")
    assert any(kind == "gather" for kind, *_ in ref_bad), ref_bad


# ---------------------------------------------------------------------------
# sharded engine under the 8-device harness
# ---------------------------------------------------------------------------


@multidevice
def test_engine_sharded_matches_single_device():
    """The whole engine pipeline (chunk prefill + burst decode over the
    shared pool) tensor-parallel over 8 devices produces the single-mesh
    tokens exactly."""
    cfg = _smoke("llama3-8b", "int8")
    params, _ = split_tree(model_init(jax.random.PRNGKey(0), cfg))
    prompts = _prompts(cfg, [10, 6, 13], seed=5)
    gen = 5
    outs = {}
    for name, mesh in (("single", single_mesh()), ("tp", tp_mesh())):
        reqs = [Request(rid=i, tokens=p, max_new=gen, arrival=0.0)
                for i, p in enumerate(prompts)]
        eng = Engine(cfg, slots=2, total_pages=12, page_size=8, max_pages=4,
                     chunk=16, burst=4, mesh=mesh,
                     kernel_backend="interpret", params=params)
        stats = eng.run(reqs, timeout_s=600)
        assert stats["all_completed"], (name, stats)
        outs[name] = {r["rid"]: r["tokens"] for r in stats["records"]}
    assert outs["tp"] == outs["single"]


@multidevice
def test_engine_sharded_run_after_warmup_compiles_nothing():
    """On a tensor-parallel mesh too, warmup() leaves nothing to compile."""
    cfg = _smoke("llama3-8b", "int8")
    params, _ = split_tree(model_init(jax.random.PRNGKey(0), cfg))
    eng = Engine(cfg, slots=2, total_pages=12, page_size=8, max_pages=4,
                 chunk=16, burst=4, mesh=tp_mesh(),
                 kernel_backend="interpret", params=params)
    eng.warmup()
    assert eng.compile_counts() == {"chunk": 1, "decode": 1, "burst": 1}
    reqs = [Request(rid=i, tokens=p, max_new=6, arrival=0.0)
            for i, p in enumerate(_prompts(cfg, [10, 6, 13], seed=5))]
    stats = eng.run(reqs, timeout_s=600)
    assert stats["all_completed"], stats
    assert eng.compile_counts() == {"chunk": 1, "decode": 1, "burst": 1}


@multidevice
def test_paged_decode_kernel_sharded_matches_ref():
    """Fused paged decode under shard_map (kv heads over 'model') matches
    the unsharded gather oracle."""
    b, ps, np_, tp_, nh, nkv, hd = 2, 8, 4, 7, 16, 8, 16
    rng = np.random.default_rng(2)
    q = jax.random.normal(jax.random.PRNGKey(0), (b, nh, hd))
    kp = jax.random.normal(jax.random.PRNGKey(1), (tp_, ps, nkv, hd),
                           jnp.bfloat16)
    vp = jax.random.normal(jax.random.PRNGKey(2), (tp_, ps, nkv, hd),
                           jnp.bfloat16)
    pt = _page_table(rng, b, np_, tp_)
    pos = jnp.asarray([np_ * ps - 1, 13], jnp.int32)
    sc = 1.0 / hd ** 0.5
    y_ref = qattention("paged_decode", q, kp, vp, pt, pos, logit_scale=sc,
                       backend="ref")
    with dispatch.shard_scope(tp_mesh()):
        y_sh = qattention("paged_decode", q, kp, vp, pt, pos,
                          logit_scale=sc, backend="interpret")
    assert _cos(y_sh, y_ref) > 0.9999
    assert _maxerr(y_sh, y_ref) < 3e-5


# ---------------------------------------------------------------------------
# hardening (PR 7): deadlines, timeout drain, retries, shedding, quarantine,
# preemption, and the page-pool invariant audit under seeded chaos
# ---------------------------------------------------------------------------

from repro.distributed.fault_tolerance import PreemptionGuard  # noqa: E402
from repro.launch.engine import TERMINAL_STATUSES  # noqa: E402
from repro.robustness import NO_FAULTS, FaultPlan  # noqa: E402


@pytest.fixture(scope="module")
def hardened():
    """One compiled engine shared by the robustness tests (they vary only
    host-side knobs — faults, budgets, guards — never compiled shapes).
    Pool: 7 usable pages, 2 slots, 5-page tables."""
    cfg = _smoke("llama3-8b", "int8")
    params, _ = split_tree(model_init(jax.random.PRNGKey(0), cfg))
    eng = Engine(cfg, slots=2, total_pages=8, page_size=8, max_pages=5,
                 chunk=16, burst=4, kernel_backend="interpret",
                 params=params)
    eng.warmup()
    return cfg, params, eng


@pytest.fixture
def heng(hardened):
    cfg, params, eng = hardened
    yield cfg, params, eng
    eng.faults = NO_FAULTS
    eng.admission_budget = None
    eng.max_retries = 2
    eng._guard = None
    eng.audit_every = False


def _trace(cfg, plens, gens, gap=0.0, seed=7, deadline=None):
    prompts = _prompts(cfg, plens, seed=seed)
    return [Request(rid=i, tokens=p, max_new=g, arrival=gap * i,
                    deadline_s=deadline)
            for i, (p, g) in enumerate(zip(prompts, gens))]


def test_engine_run_after_warmup_compiles_nothing(heng):
    """warmup() calls each step once; params and pools are committed to
    the plan layout, so no later call of a run may compile again."""
    cfg, _, eng = heng
    assert eng.compile_counts() == {"chunk": 1, "decode": 1, "burst": 1}
    stats = eng.run(_trace(cfg, [10, 20, 6], [6, 3, 9]), timeout_s=600)
    assert stats["all_completed"], stats
    assert stats["chunk_steps"] and stats["decode_steps"]
    assert eng.compile_counts() == {"chunk": 1, "decode": 1, "burst": 1}


def test_engine_global_timeout_returns_instead_of_raising(heng):
    """timeout_s is a drain guard: on expiry run() returns the stats dict
    with every request in a terminal 'timeout' status — never raises."""
    cfg, params, eng = heng
    stats = eng.run(_trace(cfg, [10, 6], [6, 6]), timeout_s=0.0)
    assert stats["drained"] == "timeout"
    assert len(stats["records"]) == 2
    assert all(r["status"] == "timeout" for r in stats["records"])
    assert not stats["all_completed"]
    assert stats["page_audit"]["ok"], stats["page_audit"]


def test_engine_mid_run_timeout_keeps_partial_results(heng):
    """A straggler tick pushes the run past timeout_s mid-decode: the drain
    cancels in-flight work but keeps the tokens already generated."""
    cfg, params, eng = heng
    eng.faults = FaultPlan(0, {"engine.straggler": {"at": (1,),
                                                    "delay_s": 2.0}})
    stats = eng.run(_trace(cfg, [10, 6], [16, 16]), timeout_s=0.8)
    assert stats["drained"] == "timeout"
    assert len(stats["records"]) == 2
    assert {r["status"] for r in stats["records"]} == {"timeout"}
    assert any(r["tokens"] for r in stats["records"]), stats["records"]
    assert stats["page_audit"]["ok"], stats["page_audit"]


def test_engine_deadline_cancels_inflight_request(heng):
    """A per-request deadline expires mid-decode (straggler-stretched
    tick): that request alone is cancelled with partial tokens; its
    deadline-free sibling completes token-identically to a clean run."""
    cfg, params, eng = heng
    prompts = _prompts(cfg, [10, 6], seed=5)
    clean = eng.run([Request(0, prompts[0], 10),
                     Request(1, prompts[1], 24)], timeout_s=600)
    assert clean["all_completed"]
    clean_toks = {r["rid"]: r["tokens"] for r in clean["records"]}

    eng.faults = FaultPlan(0, {"engine.straggler": {"at": (2,),
                                                    "delay_s": 1.0}})
    stats = eng.run([Request(0, prompts[0], 10),
                     Request(1, prompts[1], 24, deadline_s=0.5)],
                    timeout_s=600)
    rec = {r["rid"]: r for r in stats["records"]}
    assert rec[1]["status"] == "timeout" and rec[1]["reason"] == "deadline"
    assert stats["deadline_cancels"] >= 1
    assert rec[0]["status"] == "completed"
    assert rec[0]["tokens"] == clean_toks[0]
    assert stats["page_audit"]["ok"], stats["page_audit"]


def test_engine_admission_budget_sheds_overload(heng):
    """Arrivals beyond the admission budget are rejected immediately with
    a structured 'overload' record instead of growing the backlog."""
    cfg, params, eng = heng
    eng.admission_budget = 2
    stats = eng.run(_trace(cfg, [8] * 5, [4] * 5), timeout_s=600)
    st = stats["statuses"]
    assert st.get("rejected", 0) == 3 and stats["shed"] == 3, st
    assert st.get("completed", 0) == 2, st
    shed = [r for r in stats["records"] if r["status"] == "rejected"]
    assert all(r["reason"] == "overload" for r in shed)
    assert stats["page_audit"]["ok"], stats["page_audit"]


def test_engine_nan_quarantine_isolates_one_slot(heng):
    """NaNs injected into one slot's KV page trip the in-graph non-finite
    guard for that slot only: it fails with reason 'non_finite', the other
    slot's output stays token-for-token identical to the clean run, and
    the poisoned pages are scrubbed before reuse."""
    cfg, params, eng = heng
    reqs = _trace(cfg, [10, 6], [12, 12], seed=9)
    clean = eng.run(reqs, timeout_s=600)
    assert clean["all_completed"]
    clean_toks = {r["rid"]: r["tokens"] for r in clean["records"]}

    eng.faults = FaultPlan(3, {"engine.nan_logits": {"at": (0,)}})
    stats = eng.run(reqs, timeout_s=600)
    rec = {r["rid"]: r for r in stats["records"]}
    assert rec[0]["status"] == "failed" and rec[0]["reason"] == "non_finite"
    assert stats["quarantined"] == 1 and stats["nan_injections"] == 1
    assert rec[1]["status"] == "completed"
    assert rec[1]["tokens"] == clean_toks[1], "bystander slot corrupted"
    assert stats["page_audit"]["ok"], stats["page_audit"]
    assert not eng._poisoned, "poisoned pages must be scrubbed + reclaimed"


def test_engine_step_failure_retries_then_recovers(heng):
    """An injected step failure requeues its participants; the retry
    recomputes from scratch and the final tokens match the clean run."""
    cfg, params, eng = heng
    reqs = _trace(cfg, [10, 6], [8, 8], seed=2)
    clean = eng.run(reqs, timeout_s=600)
    assert clean["all_completed"]
    clean_toks = {r["rid"]: r["tokens"] for r in clean["records"]}

    eng.faults = FaultPlan(0, {"engine.step": {"at": (0,)}})
    stats = eng.run(reqs, timeout_s=600)
    assert stats["all_completed"], stats["statuses"]
    assert stats["step_failures"] == 1 and stats["retries"] >= 1
    got = {r["rid"]: r["tokens"] for r in stats["records"]}
    assert got == clean_toks
    assert stats["page_audit"]["ok"], stats["page_audit"]


def test_engine_step_failure_budget_exhausts_to_failed(heng):
    """A step that fails on every launch burns the per-request retry
    budget and ends in 'failed' — with every page back in the pool."""
    cfg, params, eng = heng
    eng.faults = FaultPlan(0, {"engine.step": {"prob": 1.0}})
    stats = eng.run(_trace(cfg, [8], [4]), timeout_s=600)
    (rec,) = stats["records"]
    assert rec["status"] == "failed" and "step_failure" in rec["reason"]
    assert stats["retries"] == eng.max_retries + 1
    assert stats["page_audit"]["ok"], stats["page_audit"]
    assert stats["page_audit"]["free"] == eng.total_pages - 1


def test_engine_preemption_guard_drains_gracefully(heng):
    """A pre-flagged PreemptionGuard flips the engine straight into drain:
    nothing is admitted, every waiting request gets a structured
    'rejected/preempted' record."""
    cfg, params, eng = heng
    guard = PreemptionGuard(signals=())
    guard.request()
    eng._guard = guard
    stats = eng.run(_trace(cfg, [8, 8], [4, 4]), timeout_s=600)
    assert stats["preempted"] and stats["drained"] == "preempted"
    assert all(r["status"] == "rejected" and r["reason"] == "preempted"
               for r in stats["records"])
    assert stats["page_audit"]["ok"], stats["page_audit"]


def test_engine_seeded_chaos_trace_contract(heng):
    """The PR 7 acceptance trace: an eviction-heavy seeded load under a
    FaultPlan injecting page-allocation failures, a step failure, a NaN
    burst and a mid-run preemption.  Contract: run() returns, every
    request ends in exactly one terminal status, fault-untouched requests
    are token-for-token identical to the clean run, and the page-pool
    audit is clean after every recovery path and at exit."""
    cfg, params, eng = heng
    # two concurrent 5-page requests overcommit the 7-page pool with
    # overlapping starvation windows: the clean run must already exercise
    # stall/evict/recompute
    reqs = _trace(cfg, [8, 8, 10, 8, 9], [32, 32, 12, 24, 8],
                  gap=0.02, seed=13)
    eng.audit_every = True
    clean = eng.run(reqs, timeout_s=600)
    assert clean["all_completed"], clean["statuses"]
    assert clean["evictions"] > 0, "trace was sized to force eviction"
    assert "audit_failures" not in clean, clean["audit_failures"]
    clean_toks = {r["rid"]: r["tokens"] for r in clean["records"]}

    eng.faults = FaultPlan(17, {
        "engine.page_alloc": {"prob": 0.2, "max_fires": 5},
        "engine.step": {"at": (2,)},
        "engine.nan_logits": {"at": (1,)},
        "engine.preempt": {"at": (12,)},
    })
    stats = eng.run(reqs, timeout_s=600)

    records = stats["records"]
    assert len(records) == len(reqs)
    assert sorted(r["rid"] for r in records) == list(range(len(reqs)))
    assert all(r["status"] in TERMINAL_STATUSES for r in records)
    assert sum(stats["statuses"].values()) == len(reqs)
    for r in records:
        if r["status"] == "completed":
            assert r["tokens"] == clean_toks[r["rid"]], (
                f"rid={r['rid']} diverged from the clean run")
    assert "audit_failures" not in stats, stats["audit_failures"]
    assert stats["page_audit"]["ok"], stats["page_audit"]
    fired = stats["faults"]["fired"]
    assert fired["engine.page_alloc"] + fired["engine.step"] > 0, fired


def test_engine_page_audit_detects_corruption(heng):
    """The audit helper itself must catch double-ownership — a free-list
    duplicate flips ok=False with a named issue."""
    cfg, params, eng = heng
    assert eng.audit_pages()["ok"]
    eng._free_pages.append(eng._free_pages[0])
    a = eng.audit_pages()
    assert not a["ok"] and any("duplicate" in s for s in a["issues"]), a
    eng._free_pages.pop()
    assert eng.audit_pages()["ok"]


# ---------------------------------------------------------------------------
# spans and the compile counter of Engine.run
# ---------------------------------------------------------------------------

_SPANS = ("engine.start", "engine.tick", "engine.intake", "engine.admit",
          "engine.claim", "engine.pack", "engine.dispatch", "engine.fetch",
          "engine.commit", "engine.finish")


@pytest.mark.parametrize("fresh_jit", [False, True])
def test_engine_spans_and_compile_count(heng, fresh_jit):
    """Every phase of a run is a span; the engine.step spans count the
    launches of each plan and sum to prefill_ms / decode_ms; no self time
    is negative.  After warmup() nothing compiles in the window, unless a
    jit the engine has not run yet is called there."""
    cfg, _, eng = heng
    if fresh_jit:
        split = jax.jit(lambda k: jax.random.split(k))

        def split_key():
            eng._key, sub = split(eng._key)
            return sub

        eng._split_key = split_key
    try:
        stats = eng.run(_trace(cfg, [10, 20, 6], [6, 3, 9]), timeout_s=600)
    finally:
        eng.__dict__.pop("_split_key", None)
    assert stats["all_completed"], stats
    sp = stats["spans"]
    assert set(_SPANS) <= set(sp), sorted(sp)
    steps = {p: sp.get(f"engine.step.{p}", {"count": 0, "ms": 0.0})
             for p in ("chunk", "decode", "burst")}
    launches = sum(r["count"] for r in steps.values())
    assert steps["chunk"]["count"] == stats["chunk_steps"] > 0
    assert (steps["decode"]["count"] + eng.burst * steps["burst"]["count"]
            == stats["decode_steps"] > 0)
    assert sp["engine.dispatch"]["count"] == sp["engine.fetch"]["count"] \
        == launches
    assert sp["engine.start"]["count"] == sp["engine.finish"]["count"] == 1
    assert stats["prefill_ms"] == steps["chunk"]["ms"]
    assert stats["decode_ms"] == steps["decode"]["ms"] + steps["burst"]["ms"]
    for name, rec in sp.items():
        assert rec["ms"] >= rec["self_ms"] >= -1e-9, (name, rec)
    if fresh_jit:
        assert stats["compiles"] > 0
    else:
        assert stats["compiles"] == 0


def test_engine_counts_decode_pages(heng):
    """stats["decode_pages"]: ``live`` is Σ⌈(pos+1)/ps⌉ over the live rows
    of every decode step (each step of a burst one token further on),
    ``window`` their rows × max_pages; each decode launch's engine.step
    carries its own count."""
    cfg, _, eng = heng
    launches = []
    launch = eng._launch

    def record(plan, step, host, participants, queue, **kw):
        if plan != "chunk":
            rows = [eng._slots.index(s) for s in participants]
            launches.append((plan, np.asarray(host[2])[rows].copy(),
                             kw.get("pages")))
        return launch(plan, step, host, participants, queue, **kw)

    eng._launch = record
    try:
        stats = eng.run(_trace(cfg, [10, 20, 6], [6, 3, 9]), timeout_s=600)
    finally:
        del eng._launch
    assert stats["all_completed"], stats
    assert launches
    live = window = steps = 0
    for plan, pos, tagged in launches:
        n = eng.burst if plan == "burst" else 1
        need = sum(-(-(p + j + 1) // eng.page_size)
                   for p in pos for j in range(n))
        assert tagged == need, (plan, pos, tagged)
        live += need
        window += len(pos) * eng.max_pages * n
        steps += n
    assert steps == stats["decode_steps"]
    assert stats["decode_pages"] == {"live": live, "window": window}
    assert 0 < live < window


def test_engine_spans_reach_the_profiler(heng, tmp_path):
    """Under a profiler the engine's spans are host events of the trace,
    each step tagged with its plan."""
    import glob

    from jax.profiler import ProfileData

    cfg, _, eng = heng
    with jax.profiler.trace(str(tmp_path)):
        stats = eng.run(_trace(cfg, [10, 6], [4, 4]), timeout_s=600)
    assert stats["all_completed"], stats
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    events = [ev for p in ProfileData.from_file(path).planes
              for ln in p.lines for ev in ln.events]
    names = {ev.name for ev in events}
    assert set(_SPANS) | {"engine.step"} <= names, sorted(
        n for n in names if n.startswith("engine."))
    plans = {dict(ev.stats).get("plan") for ev in events
             if ev.name == "engine.step"}
    assert plans and plans <= {"chunk", "decode", "burst"}, plans
    pages = [dict(ev.stats).get("pages") for ev in events
             if ev.name == "engine.step"
             and dict(ev.stats).get("plan") != "chunk"]
    assert sum(pages) == stats["decode_pages"]["live"] > 0, pages
