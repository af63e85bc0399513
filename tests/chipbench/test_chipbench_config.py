"""The key-by-key check of a configuration file against what the program
states, and a tiny latent-attention, mixture-of-experts cell driven
through the serve job on the CPU once its file agrees."""
import copy
import json
from pathlib import Path

import pytest
from chipbench_tiny import (LATENT_KEYS, ROOT, latent_model_cfg, latent_root,
                            register_latent)

from chipbench import harness, published

QWEN = json.loads((ROOT / "chipbench" / "configs" / "qwen3-8b-nf4.json")
                  .read_text())
# what the program cannot state of DeepSeek-V2-Lite yet, in file order
NOT_STATED = ["first_k_dense_replace", "max_position_embeddings",
              "model_type", "n_group", "n_shared_experts", "norm_topk_prob",
              "rope_scaling", "routed_scaling_factor", "scoring_func",
              "seq_aux", "topk_group", "topk_method"]
# of those, the ones the required work reads: never let through unchecked
NOT_STATED_SHAPE = ["first_k_dense_replace", "n_shared_experts"]


def test_qwen_file_passes():
    m = harness.model_config(QWEN)
    assert (m.num_layers, m.d_model, m.head_dim) == (36, 4096, 128)
    assert set(QWEN["unchecked"]) == {"model_type", "torch_dtype",
                                      "max_position_embeddings"}


def test_absent_head_dim_takes_the_programs_default():
    cfg = {k: v for k, v in QWEN.items() if k != "head_dim"}
    m = harness.model_config(cfg)
    assert m.head_dim is None and m.resolved_head_dim == 4096 // 32
    cfg["head_dim"] = None
    assert harness.model_config(cfg).resolved_head_dim == 128


@pytest.mark.parametrize("key,value,said", [
    ("attention_bias", True, "the program False"),
    ("hidden_act", "gelu", "the program 'silu'"),
    ("sliding_window", 4096, "the program does not state it"),
    ("torch_dtype", "float32", None),
])
def test_a_disagreeing_or_unstated_key_is_refused(key, value, said):
    cfg = copy.deepcopy(QWEN)
    cfg[key] = value
    if said is None:      # unchecked keys are not compared
        harness.model_config(cfg)
        return
    with pytest.raises(published.ConfigMismatch) as e:
        harness.model_config(cfg)
    assert f"{key}: the file says {value!r}, {said}" in str(e.value)


@pytest.mark.parametrize("unchecked,fault", [
    ({"model_type": ""}, "one-line reason"),
    ({"model_type": "two\nlines"}, "one-line reason"),
    ({"model_type": 3}, "one-line reason"),
    ({"rope_scaling": "absent from the file"}, "not a published key"),
    ({"reference": "a schema key"}, "not a published key"),
])
def test_unchecked_needs_a_reason_and_a_key_of_the_file(unchecked, fault):
    cfg = copy.deepcopy(QWEN)
    cfg["unchecked"] = dict(cfg["unchecked"], **unchecked)
    with pytest.raises(published.ConfigMismatch, match=fault):
        harness.model_config(cfg)


def test_unchecked_cannot_hide_a_disagreement():
    cfg = copy.deepcopy(QWEN)
    cfg["hidden_act"] = "gelu"
    cfg["unchecked"]["hidden_act"] = "trying to skip it"
    with pytest.raises(published.ConfigMismatch, match="hidden_act"):
        harness.model_config(cfg)


def test_the_programs_own_statement_is_preferred(monkeypatch):
    import repro.configs

    cfg = copy.deepcopy(QWEN)
    cfg["sliding_window"] = None

    def program_published(m):
        return dict(published.published(m), sliding_window=None)

    monkeypatch.setattr(repro.configs, "published", program_published,
                        raising=False)
    harness.model_config(cfg)


def test_latent_file_is_refused_naming_each_key(tmp_path, monkeypatch):
    register_latent(monkeypatch)
    cell = harness.load_cell("tiny.latent", root=latent_root(Path(tmp_path)))
    with pytest.raises(published.ConfigMismatch) as e:
        harness.model_config(cell.cfg)
    faults = str(e.value).split(": ", 1)[1].split("; ")
    named = [f.split(":")[0] for f in faults]
    assert named[0] == "first_k_dense_replace"
    assert sorted(named) == sorted(NOT_STATED + ["q_lora_rank"])
    assert "q_lora_rank: the file says None, the program 32" in str(e.value)
    for key in NOT_STATED_SHAPE:
        assert f"{key}: the file says {LATENT_KEYS[key]!r}, the program " \
            "does not state it (the required work reads it)" in str(e.value)
    # every other published key is stated, and agrees
    stated = published.published(latent_model_cfg())
    assert set(LATENT_KEYS) - set(NOT_STATED) <= set(stated)


def latent_unchecked():
    """Every key the program does not state but the required work does not
    read, each with a reason."""
    return {k: "the program does not state it yet" for k in NOT_STATED
            if k not in NOT_STATED_SHAPE}


@pytest.mark.parametrize("key", NOT_STATED_SHAPE)
def test_unchecked_cannot_cover_a_key_the_work_reads(tmp_path, monkeypatch,
                                                     key):
    register_latent(monkeypatch)
    unchecked = dict(latent_unchecked(), **{key: "the program lacks it"})
    drop = [k for k in NOT_STATED_SHAPE if k != key]
    root = latent_root(Path(tmp_path), drop=drop, q_lora_rank=32,
                       unchecked=unchecked)
    cell = harness.load_cell("tiny.latent", root=root)
    with pytest.raises(published.ConfigMismatch) as e:
        harness.model_config(cell.cfg)
    faults = str(e.value).split(": ", 1)[1].split("; ")
    assert faults == [f"unchecked {key!r}: the required work reads it, so "
                      "the program has to state it"]


@pytest.mark.parametrize("key,said", [
    ("kv_lora_rank", 16), ("qk_rope_head_dim", 8), ("n_routed_experts", 8),
    ("num_experts_per_tok", 2), ("moe_intermediate_size", 32),
    ("moe_layer_freq", 1), ("hidden_act", "silu")])
def test_a_key_the_program_states_cannot_be_left_out(tmp_path, monkeypatch,
                                                     key, said):
    """Without it the work would count grouped-query attention or dense
    MLPs for a program that runs MLA and experts."""
    register_latent(monkeypatch)
    root = latent_root(Path(tmp_path), drop=NOT_STATED_SHAPE + [key],
                       q_lora_rank=32, unchecked=latent_unchecked())
    cell = harness.load_cell("tiny.latent", root=root)
    with pytest.raises(published.ConfigMismatch) as e:
        harness.model_config(cell.cfg)
    faults = str(e.value).split(": ", 1)[1].split("; ")
    assert faults == [f"{key}: the file leaves it out, the program {said!r}"]


def test_experts_off_the_published_grid_are_refused():
    """The program's expert layers at ``every`` 2 lie at odd layers, which
    no ``moe_layer_freq`` describes: the file is refused with it or
    without it."""
    from repro.configs import MoECfg

    m = latent_model_cfg().with_(
        norm_eps=LATENT_KEYS["rms_norm_eps"],
        moe=MoECfg(num_experts=8, top_k=2, d_ff=32, every=2))
    said = published.published(m)["moe_layer_freq"]
    assert said == "layers i % 2 == 1"
    cfg = dict(LATENT_KEYS, name="tiny-latent", q_lora_rank=32,
               unchecked=latent_unchecked())
    for k in NOT_STATED_SHAPE:
        del cfg[k]
    for value in (1, 2, None):
        if value is None:
            del cfg["moe_layer_freq"]
            want = f"moe_layer_freq: the file leaves it out, the program " \
                f"{said!r}"
        else:
            cfg["moe_layer_freq"] = value
            want = f"moe_layer_freq: the file says {value}, the program " \
                f"{said!r}"
        with pytest.raises(published.ConfigMismatch) as e:
            published.check(cfg, m)
        assert str(e.value).split(": ", 1)[1] == want


def test_agreeing_latent_cell_runs_set_up_window_and_observe(tmp_path,
                                                             monkeypatch):
    register_latent(monkeypatch)
    # the program has no leading dense layer and no shared expert: the
    # file agrees by leaving both out; the keys the work does not read are
    # listed as unchecked
    root = latent_root(Path(tmp_path), drop=NOT_STATED_SHAPE, q_lora_rank=32,
                       unchecked=latent_unchecked())
    cell = harness.load_cell("tiny.latent", root=root)
    ctx = harness.RunContext(cell=cell, seed=2**31 + 9, seconds=3.0,
                             backend="ref")
    ctx.model_cfg = harness.model_config(cell.cfg)
    assert ctx.model_cfg.attn_kind == "mla" and ctx.model_cfg.moe
    job = harness.load_module(harness.HERE / "jobs" / "serve.py").Job(ctx)
    job.setup(3.0)
    job.window(3.0)
    obs = job.observe()
    job.free()
    w, s = obs["work"], obs["shape"]
    # counted as the program runs it: both layers with experts, none shared
    assert s.mla and s.moe_layers == 2 and s.dense_layers == 0
    assert s.shared == 0
    assert w["rows.prefill"] > 0 and w["rows.decode"] > 0
    st = obs["stats"]
    for phase, rows, steps in (("prefill", "rows.prefill", "chunk_steps"),
                               ("decode", "rows.decode", "decode_steps")):
        e = w[f"experts.{phase}"]
        assert e.flops == 2 * w[rows] * 2 * s.expert_macs * 2
        assert e.bytes >= st[steps] * 2 * s.expert_bytes * 2
    assert obs["admitted"] > 0 and st["step_failures"] == 0
