"""What a ``ModelConfig`` states under the published ``config.json`` key
names, for the benchmark's key-by-key check of a configuration file.

The dense keys and the latent-attention and expert sizes are stated for
every entry; each DeepSeek-style setting (shared experts, leading dense
layers, the router's scoring, renormalisation, scale and grouping, a rope
scaling) only where the entry sets it, so an entry that sets none states
no more than before those settings existed.
"""
from __future__ import annotations

__all__ = ["published"]

# MoECfg field -> published key, stated where the field is set
_MOE_KEYS = (("first_dense", "first_k_dense_replace"),
             ("n_shared", "n_shared_experts"),
             ("scoring_func", "scoring_func"),
             ("norm_topk_prob", "norm_topk_prob"),
             ("routed_scaling_factor", "routed_scaling_factor"),
             ("topk_method", "topk_method"),
             ("n_group", "n_group"),
             ("topk_group", "topk_group"))


def published(cfg) -> dict:
    """Every value ``cfg`` determines, under the published names.
    ``head_dim`` None is the program's default, d / heads.
    ``moe_layer_freq`` is 1 where every layer after the leading dense ones
    has experts; at a larger ``every`` the program puts its expert layers
    at ``layer % every == every - 1``, off the published grid ``layer %
    moe_layer_freq == 0``, which it states in words that no file's number
    equals."""
    out = {
        "num_hidden_layers": cfg.num_layers,
        "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "intermediate_size": cfg.d_ff,
        "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "hidden_act": "silu",          # every MLP and expert is a SwiGLU
        "attention_bias": False,       # no projection has a bias
    }
    if cfg.attn_kind == "mla":
        m = cfg.mla
        out.update(q_lora_rank=m.q_lora_rank, kv_lora_rank=m.kv_lora_rank,
                   qk_nope_head_dim=m.qk_nope_dim,
                   qk_rope_head_dim=m.qk_rope_dim, v_head_dim=m.v_head_dim)
    if cfg.moe is not None:
        mo = cfg.moe
        out.update(n_routed_experts=mo.num_experts,
                   num_experts_per_tok=mo.top_k,
                   moe_intermediate_size=mo.d_ff)
        out["moe_layer_freq"] = 1 if mo.every == 1 else (
            f"layers i % {mo.every} == {mo.every - 1}")
        for field, key in _MOE_KEYS:
            value = getattr(mo, field)
            if value is not None:
                out[key] = value
    if cfg.rope_scaling is not None:
        out["rope_scaling"] = cfg.rope_scaling.published()
    return out
