"""DeepSeek-V2-Lite's configuration against the program and the plain
reference (``references/deepseek_v2.py``) on the CPU, at the registry
entry's ``smoke_variant`` size (``ref`` kernels): no q LoRA, YaRN at factor
40, one leading dense layer and two expert layers of 4 experts (top-2) with
2 shared experts.

Tolerances.  The program computes in bfloat16 with float32 accumulation;
the reference in float32 throughout, routing on its own logits.  At this
size the widest logit gap of the served tokens reads 0, 0.0027, 0.0106,
0.0114 and 0.056 over five seeds and the float8 control 0.149 to 0.219, so
the tiny cell holds the limit 0.1, between the two with room on both
sides.  Logits of chunked prefill and paged
decode (about 0.6 wide) agree with the reference's to 4e-2 absolute: they
read 0.023 at the test's seed, from bf16 activations, B·A in bf16 and the
int8 latent cache, against 0.28 for the reference in float8.  (The
contiguous path in float32 reads 8e-4: bf16 B·A alone.)
"""
import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_tiny import ROOT, tiny_root

import repro.configs
from chipbench import harness, published, weights
from repro.configs import get_config, list_configs, smoke_variant
from repro.configs import base as config_base

FILE = json.loads((ROOT / "chipbench" / "configs" /
                   "deepseek-v2-lite-nf4.json").read_text())
TINY = "chipbench-tiny-deepseek"
LIMIT = 0.1
SEED = 2**31 + 17
# what the program states for DeepSeek-V2-Lite beyond the legacy keys
NEW_KEYS = {"first_k_dense_replace", "n_shared_experts", "norm_topk_prob",
            "scoring_func", "routed_scaling_factor", "topk_method",
            "n_group", "topk_group", "rope_scaling"}


def tiny_cfg():
    return smoke_variant(get_config("deepseek-v2-lite"))


def tiny_file() -> dict:
    """The DeepSeek-V2-Lite file with every size cut to ``tiny_cfg``."""
    m = tiny_cfg()
    c = copy.deepcopy(FILE)
    c.update(name="tiny-deepseek", registry=TINY, hidden_size=m.d_model,
             intermediate_size=m.d_ff, num_hidden_layers=m.num_layers,
             num_attention_heads=m.num_heads,
             num_key_value_heads=m.num_kv_heads, head_dim=m.head_dim,
             vocab_size=m.vocab_size, kv_lora_rank=m.mla.kv_lora_rank,
             qk_nope_head_dim=m.mla.qk_nope_dim,
             qk_rope_head_dim=m.mla.qk_rope_dim, v_head_dim=m.mla.v_head_dim,
             n_routed_experts=m.moe.num_experts,
             num_experts_per_tok=m.moe.top_k,
             moe_intermediate_size=m.moe.d_ff)
    c["quantization"] = dict(c["quantization"], block_size=32)
    return c


@pytest.fixture
def root(tmp_path, monkeypatch):
    """``tiny_root`` plus a cell ``tiny.deepseek``: the batch-decode mix on
    ``tiny_file`` over the registry entry ``TINY``."""
    monkeypatch.setitem(config_base._REGISTRY, config_base._norm(TINY),
                        tiny_cfg)
    r = tiny_root(Path(tmp_path), LIMIT)
    (r / "chipbench" / "configs" / "tiny-deepseek.json").write_text(
        json.dumps(tiny_file()))
    (r / "chipbench" / "workloads" / "tiny.deepseek.json").write_text(
        json.dumps({"limits": {"widest_logit_gap": LIMIT}}))
    spec = json.loads((r / "BENCHMARK.json").read_text())
    spec["workloads"].append(dict(name="tiny.deepseek",
                                  config="tiny-deepseek",
                                  traffic="batch-decode", chips=1,
                                  why="tiny"))
    (r / "BENCHMARK.json").write_text(json.dumps(spec))
    return r


def _sets_new_fields(cfg) -> bool:
    mo = cfg.moe
    return cfg.rope_scaling is not None or (mo is not None and any(
        getattr(mo, f) is not None for f in (
            "n_shared", "first_dense", "scoring_func", "norm_topk_prob",
            "routed_scaling_factor", "topk_method", "n_group",
            "topk_group")))


@pytest.mark.parametrize("name", list_configs())
def test_program_statement_equals_the_benchmarks_for_legacy_entries(name):
    cfg = get_config(name)
    said = repro.configs.published(cfg)
    legacy = published.published(cfg)
    assert {k: said[k] for k in legacy} == legacy
    if _sets_new_fields(cfg):
        assert set(said) - set(legacy) == NEW_KEYS
    else:
        assert said == legacy


def test_deepseek_file_passes_with_three_unchecked_keys():
    m = harness.model_config(FILE)
    assert m.name == "deepseek-v2-lite" and m.lead_layers == 1
    assert m.mla.q_lora_rank is None and m.moe.n_shared == 2
    assert set(FILE["unchecked"]) == {"model_type", "max_position_embeddings",
                                      "seq_aux"}
    assert FILE["reduced"] == []
    said = repro.configs.published(m)
    assert said["rope_scaling"] == FILE["rope_scaling"]
    for key in ("first_k_dense_replace", "n_shared_experts"):
        cfg = copy.deepcopy(FILE)
        cfg[key] += 1
        with pytest.raises(published.ConfigMismatch, match=key):
            harness.model_config(cfg)


def test_every_leaf_is_drawn():
    cfg = tiny_cfg()
    p = weights.flat(weights.draw(cfg, SEED))
    assert "layers/blk0/mixer/wq/q" in p and "lead/blk0/mlp/w_down/q" in p
    assert "layers/blk0/mlp/shared/w_gate/q" in p
    assert not any("q_down" in k for k in p)
    assert all(np.isfinite(np.asarray(v, np.float32)).all()
               for v in p.values())


def _paged_logits(cfg, params, seq, prompt_len, chunk=16, page=8):
    """Logits at every position from prompt_len - 1 on: chunked prefill of
    the prompt, then paged decode of the rest of ``seq`` one token a
    step."""
    from repro.models import (forward_decode_paged, forward_prefill_chunk,
                              paged_cache_init, split_tree)

    n = len(seq)
    npages = -(-(n + chunk) // page)
    pools, _ = split_tree(paged_cache_init(cfg, npages + 1, page))
    pt = jnp.arange(1, npages + 1, dtype=jnp.int32)[None]
    tokens = np.asarray(seq, np.int32)
    out = []
    for c0 in range(0, prompt_len, chunk):
        seg = tokens[c0:min(c0 + chunk, prompt_len)]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(seg)] = seg
        qpos = np.full((1, chunk), -1, np.int32)
        qpos[0, :len(seg)] = c0 + np.arange(len(seg))
        logits, pools = forward_prefill_chunk(
            params, cfg, {"tokens": jnp.asarray(toks)}, pools, pt,
            jnp.asarray(qpos), jnp.asarray([c0], jnp.int32))
    out.append(logits[0, -1])
    for p in range(prompt_len, n):
        logits, pools = forward_decode_paged(
            params, cfg, {"tokens": jnp.asarray(tokens[p:p + 1])}, pools,
            pt, jnp.asarray([p], jnp.int32))
        out.append(logits[0, -1])
    return np.asarray(jnp.stack(out), np.float32)[:, :cfg.vocab_size]


def test_chunked_prefill_and_paged_decode_match_the_reference(monkeypatch):
    from repro.kernels import dispatch

    monkeypatch.setitem(config_base._REGISTRY, config_base._norm(TINY),
                        tiny_cfg)
    c = tiny_file()
    cfg = harness.model_config(c)
    params = weights.draw(cfg, SEED)
    ref = harness.load_reference(c)(c, weights.flat(params))
    rng = np.random.default_rng(3)
    seq = rng.integers(0, cfg.vocab_size, 40)
    prompt = 21     # two chunks, the second partial, then 19 decode steps
    with dispatch.backend_scope("ref"):
        got = _paged_logits(cfg, params, seq, prompt)
    rows = np.arange(prompt - 1, len(seq))
    want = ref.logits([seq], [rows])[0]
    low = ref.logits([seq], [rows], control=True)[0]
    err = np.abs(got - want).max()
    assert err < 4e-2, err
    assert np.abs(low - want).max() > 4e-2


def _run(root, **kw):
    cell = harness.load_cell("tiny.deepseek", root=root)
    return harness.run_cell(cell, SEED, 3.0, False, backend="ref",
                            require_tpu=False, log=lambda *_: None, **kw)


def test_tiny_cell_is_correct_and_its_control_is_not(root):
    res = _run(root)
    assert res["correct"], res["checks"]
    assert res["checks"]["widest_logit_gap"]["value"] <= LIMIT
    assert res["attempted"] > 0 and res["failed"] == 0
    res = _run(root, control=True)
    assert res["correct"] is False
    assert res["checks"]["widest_logit_gap"]["value"] > LIMIT


def test_experts_hit_equals_a_host_recount_over_live_rows(root,
                                                          monkeypatch):
    """Every routing decision of the window is recorded on the host (the
    expert ids and the live rows of each expert layer call) and recounted:
    the engine's ``experts_hit`` and ``expert_rows`` agree, and the work
    reads them."""
    from repro.models import moe as moe_mod

    calls = []
    real = moe_mod.group_rows

    def recorded(idx, live, e, rows):
        jax.debug.callback(lambda i, lv: calls.append((np.asarray(i),
                                                       np.asarray(lv))),
                           idx, live)
        return real(idx, live, e, rows)

    monkeypatch.setattr(moe_mod, "group_rows", recorded)
    cell = harness.load_cell("tiny.deepseek", root=root)
    ctx = harness.RunContext(cell=cell, seed=SEED, seconds=3.0,
                             backend="ref")
    ctx.model_cfg = harness.model_config(cell.cfg)
    job = harness.load_module(harness.HERE / "jobs" / "serve.py").Job(ctx)
    job.setup(3.0)
    calls.clear()           # warm-up routes dead rows only
    job.window(3.0)
    obs = job.observe()
    st = obs["stats"]
    m = ctx.model_cfg
    slots = job.g["slots"]
    hit = {"prefill": 0, "decode": 0}
    assigned = {"prefill": 0, "decode": 0}
    for idx, live in calls:
        phase = "decode" if idx.shape[0] == slots else "prefill"
        hit[phase] += len(np.unique(idx[live]))
        assigned[phase] += int(live.sum()) * m.moe.top_k
    assert st["experts_hit"] == hit
    assert {p: r["assigned"] for p, r in st["expert_rows"].items()} \
        == assigned
    assert st["expert_rows"]["decode"]["assigned"] == \
        m.moe.top_k * (m.num_layers - 1) * obs["work"]["rows.decode"]
    e = obs["work"]["experts.decode"]
    s = obs["shape"]
    assert e.bytes == hit["decode"] * s.expert_bytes + \
        obs["work"]["rows.decode"] * s.top_k * s.expert_act_bytes * \
        s.moe_layers
