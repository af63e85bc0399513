"""A tiny copy of the benchmark's cells for CPU tests: the real traffic
mixes and configuration with their sizes cut to what a test run holds."""
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_root(tmp: Path, limit: float = 0.05) -> Path:
    """A benchmark root under ``tmp`` with cells ``tiny.batch`` (the
    batch-decode mix) and ``tiny.chat`` (the same sizes under open-loop
    Poisson arrivals): 2 layers of width 64, prompts 8-40, outputs 2-12."""
    here = ROOT / "chipbench"
    (tmp / "chipbench").mkdir(parents=True, exist_ok=True)
    for d in ("configs", "traffic", "workloads"):
        (tmp / "chipbench" / d).mkdir(exist_ok=True)
    c = json.loads((here / "configs" / "qwen3-8b-nf4.json").read_text())
    c.update(name="tiny", hidden_size=64, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=256)
    c["quantization"] = dict(c["quantization"], block_size=32)
    (tmp / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(c))
    batch = json.loads((here / "traffic" / "batch-decode.json").read_text())
    chat = dict(batch, arrivals={"kind": "poisson", "rate_per_s": None,
                                 "drain_cap_s": 30})
    for t, m in (("batch-decode", batch), ("chat-poisson", chat)):
        m = dict(m)
        m["prompt"] = dict(m["prompt"], median=20, min=8, max=40)
        m["output"] = dict(m["output"], median=6, min=2, max=12)
        m["engine"] = dict(m["engine"], slots=4, chunk=16, burst=4,
                           pool_tokens_per_slot=52)
        if m["arrivals"]["kind"] == "backlog":
            m["arrivals"] = dict(m["arrivals"], requests=16, block=4)
        m["check"] = {"requests": 3, "tokens": 20}
        (tmp / "chipbench" / "traffic" / f"{t}.json").write_text(
            json.dumps(m))
    lim = {"limits": {"widest_logit_gap": limit}}
    (tmp / "chipbench" / "workloads" / "tiny.batch.json").write_text(
        json.dumps(lim))
    (tmp / "chipbench" / "workloads" / "tiny.chat.json").write_text(
        json.dumps(dict(lim, arrivals={"rate_per_s": 4.0})))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [
        dict(name="tiny.batch", config="tiny", traffic="batch-decode",
             chips=1, why="tiny"),
        dict(name="tiny.chat", config="tiny", traffic="chat-poisson",
             chips=1, why="tiny")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.chat" if "chat" in w else "tiny.batch"
                              for w in m["workloads"]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def cpu_env() -> dict:
    """The environment of a child process that must find no TPU."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("REPRO_")}
    env["JAX_PLATFORMS"] = "cpu"
    return env


# DeepSeek-V2-Lite's published config.json keys (latent attention, 64
# routed experts of which 6 per token, 2 shared, one leading dense layer),
# with every size cut to a tiny width.
LATENT_KEYS = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "kv_lora_rank": 16, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 8,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "num_key_value_heads": 4, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 16,
    "vocab_size": 256,
}
LATENT_REGISTRY = "chipbench-tiny-latent"


def latent_model_cfg():
    """The program's tiny latent-attention, mixture-of-experts model at the
    widths of ``LATENT_KEYS`` (q through a LoRA of 32: the program has no
    direct q projection)."""
    from repro.configs import MLACfg, ModelConfig, MoECfg, get_config

    base = get_config("qwen3-8b")
    return ModelConfig(
        name=LATENT_REGISTRY, family="moe", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=256,
        attn_kind="mla",
        mla=MLACfg(q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16,
                   qk_rope_dim=8, v_head_dim=16),
        moe=MoECfg(num_experts=8, top_k=2, d_ff=32), rope_theta=10000.0,
        vocab_pad_multiple=64, quant=base.quant.with_(block_size=32))


def register_latent(monkeypatch):
    """Make ``latent_model_cfg`` the registry entry ``LATENT_REGISTRY`` for
    one test."""
    from repro.configs import base

    monkeypatch.setitem(base._REGISTRY, base._norm(LATENT_REGISTRY),
                        latent_model_cfg)


def latent_root(tmp: Path, drop=(), **keys) -> Path:
    """``tiny_root`` plus a cell ``tiny.latent``: the batch-decode mix on a
    configuration file of ``LATENT_KEYS`` (without ``drop``, updated by
    ``keys``) over the registry entry ``LATENT_REGISTRY``."""
    root = tiny_root(tmp)
    q = json.loads((ROOT / "chipbench" / "configs" / "qwen3-8b-nf4.json")
                   .read_text())["quantization"]
    c = dict({k: v for k, v in LATENT_KEYS.items() if k not in drop},
             name="tiny-latent", source="tiny",
             registry=LATENT_REGISTRY, quantization=dict(q, block_size=32),
             reduced=[], **keys)
    (root / "chipbench" / "configs" / "tiny-latent.json").write_text(
        json.dumps(c))
    (root / "chipbench" / "workloads" / "tiny.latent.json").write_text(
        json.dumps({"limits": {"widest_logit_gap": 0.05}}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append(dict(name="tiny.latent", config="tiny-latent",
                                  traffic="batch-decode", chips=1,
                                  why="tiny"))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
