"""The key-by-key check of a configuration file against the program.

A configuration file holds the published ``config.json``'s keys beside the
harness's own schema keys.  The harness builds the program's
``ModelConfig`` from the registry entry the file names and the file's
dense sizes; every other published key the file holds must then be stated
by the program with an equal value, or be listed in the file's
``unchecked`` object with a one-line reason.  The check runs both ways:
every key the program states must also be in the file, unless the
program states it null, which an absent key reads as.  A key that the
required work reads (``work.SHAPE_KEYS``) cannot be listed as unchecked,
so the work counts only what the program states it runs.  Otherwise the
run stops before set-up, naming each key and both values: a file that
says ``kv_lora_rank`` 512 never runs a registry entry that holds 256, and
a file that leaves it out never runs an MLA entry counted as grouped-query
attention.

What the program states is ``repro.configs.published(model_cfg)`` where
the program has that function, else ``published`` below: the values a
``ModelConfig`` determines, under the published names.  A program that
gains a mechanism (shared experts, leading dense layers, a rope scaling)
states its keys there, and this file needs no edit.
"""
from __future__ import annotations

from chipbench.work import SHAPE_KEYS

__all__ = ["SCHEMA_KEYS", "ConfigMismatch", "published", "stated", "check"]

SCHEMA_KEYS = frozenset({
    "name", "source", "registry", "registry_overrides", "quantization",
    "reference", "reduced", "assumed", "departures", "deployment",
    "published_keys_checked", "unchecked"})


class ConfigMismatch(ValueError):
    """A published key the program does not state, or states otherwise."""


def published(cfg) -> dict:
    """Every value a ``ModelConfig`` determines, under the published
    ``config.json`` names.  ``head_dim`` None is the program's default,
    d / heads.  ``moe_layer_freq`` is 1 where every layer has experts; at
    a larger ``every`` the program puts its expert layers at ``layer %
    every == every - 1``, off the published grid ``layer % moe_layer_freq
    == 0``, which it states in words that no file's number equals."""
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
    return out


def stated(model_cfg) -> dict:
    """What the program states of ``model_cfg``: its own ``published``
    where it has one, else this module's."""
    import repro.configs

    return getattr(repro.configs, "published", published)(model_cfg)


def _equal(a, b) -> bool:
    if isinstance(a, bool) or isinstance(b, bool):
        return type(a) is type(b) and a == b
    return a == b


def check(cfg: dict, model_cfg) -> None:
    """Raise ``ConfigMismatch`` naming every published key of ``cfg`` that
    the program states otherwise, or does not state and ``unchecked`` does
    not list with a reason, and every non-null key the program states that
    ``cfg`` leaves out."""
    said = stated(model_cfg)
    unchecked = cfg.get("unchecked", {})
    faults = []
    for key, why in unchecked.items():
        if key not in cfg or key in SCHEMA_KEYS:
            faults.append(f"unchecked {key!r}: not a published key of the "
                          "file")
        elif key in SHAPE_KEYS:
            faults.append(f"unchecked {key!r}: the required work reads it, "
                          "so the program has to state it")
        elif not isinstance(why, str) or not why.strip() or "\n" in why:
            faults.append(f"unchecked {key!r}: give a one-line reason")
    for key, value in cfg.items():
        if key in SCHEMA_KEYS:
            continue
        if key in said:
            if not _equal(value, said[key]):
                faults.append(f"{key}: the file says {value!r}, the program "
                              f"{said[key]!r}")
        elif key not in unchecked:
            faults.append(f"{key}: the file says {value!r}, the program "
                          "does not state it" + (
                              " (the required work reads it)"
                              if key in SHAPE_KEYS else
                              " (list it under 'unchecked' with a reason)"))
    for key, value in said.items():
        if key not in cfg and value is not None:
            faults.append(f"{key}: the file leaves it out, the program "
                          f"{value!r}")
    if faults:
        raise ConfigMismatch(
            f"configuration {cfg.get('name')!r} disagrees with the program: "
            + "; ".join(faults))
