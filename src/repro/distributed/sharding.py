"""Logical-axis sharding: rules, resolution, per-arch policies.

MaxText-style two-namespace design:
  * weight rules  — applied to the P-tree axis names from model init,
  * activation rules — applied by `repro.models.common.shard` constraints.

``resolve_spec`` enforces divisibility per dimension (a rule that doesn't
divide the actual dim is dropped with a record, which is how 40/24/14-head
archs stay compilable at TP=16) and never reuses a mesh axis twice in one
PartitionSpec.

Policies (chosen per arch × shape by ``make_rules``):
  * 1D: weights on 'model' (TP); batch on ('pod','data') — default.
  * 2D: giant models additionally shard the weights' other dim over 'data'
    (GSPMD turns that into FSDP-style gather / 2-D TP) — picked automatically
    when the quantized bytes/device under 1D exceed ``budget_gb``.
  * long-context decode: batch < data-parallelism ⇒ the KV-cache sequence dim
    shards over ('pod','data') instead of batch.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["ShardingPolicy", "make_rules", "resolve_spec", "tree_pspecs",
           "tree_shardings", "estimate_quantized_gb", "row_shard"]


def row_shard(arr, mesh):
    """Place an array with its leading axis sharded over *every* axis of
    ``mesh`` (data-parallel rows), replicating when the mesh is absent,
    trivial, or the dim does not divide.

    Used by the sharded streaming-PTQ path: placement only — the chunked
    arithmetic is fixed by the plan's virtual-shard count, so replicating
    (the fallback here) changes wall-clock, never bytes.
    """
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(arr)
    if mesh is None:
        return x
    total = int(np.prod(list(dict(mesh.shape).values())))
    if total <= 1 or x.ndim == 0 or x.shape[0] % total:
        return x
    spec = PartitionSpec(tuple(mesh.axis_names))
    return jax.device_put(x, NamedSharding(mesh, spec))


@dataclasses.dataclass
class ShardingPolicy:
    weight_rules: dict
    act_rules: dict
    dropped: list  # [(axes, dim, rule)] divisibility fallbacks (for the log)

    def summary(self) -> dict:
        """Compact layout record (StepPlan.meta / checkpoint manifests):
        which mesh axes carry weights, whether the LoRDS factors replicate
        (the codes-shard / factors-replicate invariant), whether the fused
        attention kernels can run head-sharded under shard_map (the
        head-local, psum-free qattention route needs the heads act rule on
        'model'), and how many rules were dropped to divisibility."""
        used = sorted({ax for rule in self.weight_rules.values() if rule
                       for ax in ((rule,) if isinstance(rule, str)
                                  else tuple(rule))})
        return {
            "weight_axes": used,
            "lords_factors": ("replicated"
                              if self.weight_rules.get("lords_rank") is None
                              else "sharded"),
            "attention_heads": ("model-sharded"
                                if self.act_rules.get("heads") == "model"
                                else "replicated"),
            "dropped": len(self.dropped),
        }


# logical axis names used across the model zoo
_WEIGHT_AXES_1D = {
    # dim -> mesh axis (None = replicate)
    "embed": None, "vocab": "model", "embed_vocab": None,
    "mlp": "model",
    "qkv_out": "model", "kv_out": "model",
    "q_lora": None, "kv_lora": None,
    "expert": "model", "moe_out": None, "moe_in": None,
    "mamba_in": "model", "dt_rank": None, "state": None,
    "mlstm_in": "model", "slstm_in": "model",
    "heads": None, "lords_rank": None, "layers": None,
}

# 2D variant: contract/other weight dims also shard over 'data'
_WEIGHT_AXES_2D = dict(
    _WEIGHT_AXES_1D,
    embed="data",          # second weight dim of attn/mlp matrices
    moe_in="data",         # per-expert FFN d_model dim (kimi-k2 2-D ETP)
    embed_vocab=None,
)

_ACT_AXES = {
    "batch": ("pod", "data"),
    "tokens": ("pod", "data"),
    "seq": None,
    "heads": "model", "kv_heads": "model", "head_dim": None,
    "mlp_act": "model", "mamba_act": "model",
    "vocab": "model",
    "expert": "model", "capacity": None,
    "cache_seq": None,
    # paged KV pool: pages are global (shared across slots) — never sharded
    # over data; the kv_heads dim keeps its model rule like the contiguous
    # cache it replaces
    "kv_pages": None, "page_slot": None,
    "kv_lora": None, "rope_dim": None, "state": None,
    "mlstm_in": "model", "slstm_in": "model",
}


def estimate_quantized_gb(cfg, pack: int = 2) -> float:
    """Rough quantized-model footprint (GB): params/pack + bf16 embeds."""
    d = cfg.d_model
    per_layer = 0
    for mixer, mlp in cfg.layer_kinds():
        if mixer == "attn":
            if cfg.attn_kind == "mla":
                m = cfg.mla
                qk = m.qk_nope_dim + m.qk_rope_dim
                q_proj = (d * cfg.num_heads * qk if m.q_lora_rank is None
                          else d * m.q_lora_rank
                          + m.q_lora_rank * cfg.num_heads * qk)
                per_layer += (q_proj + d * (m.kv_lora_rank + m.qk_rope_dim)
                              + m.kv_lora_rank * cfg.num_heads
                              * (m.qk_nope_dim + m.v_head_dim)
                              + cfg.num_heads * m.v_head_dim * d)
            else:
                hd = cfg.resolved_head_dim
                per_layer += (d * cfg.num_heads * hd
                              + 2 * d * cfg.num_kv_heads * hd
                              + cfg.num_heads * hd * d)
        elif mixer == "mamba":
            din = cfg.mamba.expand * d
            dtr = cfg.mamba.dt_rank or -(-d // 16)
            per_layer += d * 2 * din + din * (dtr + 2 * cfg.mamba.d_state) \
                + dtr * din + din * d
        elif mixer in ("mlstm", "slstm"):
            din = int(cfg.xlstm.proj_factor * d) if cfg.xlstm else d
            per_layer += (2 * d * din + 3 * din * din + din * d
                          if mixer == "mlstm" else 4 * d * d)
        if mlp == "dense":
            per_layer += 3 * d * cfg.d_ff
        elif mlp == "moe":
            per_layer += (cfg.moe.num_experts * 3 * d * cfg.moe.d_ff
                          + 3 * d * cfg.moe.shared_ff)
    # the leading dense layers' MLPs (their attention is left out)
    lead = cfg.lead_layers * 3 * d * cfg.d_ff
    q_bytes = (cfg.num_periods * per_layer + lead) / pack
    embed_bytes = cfg.padded_vocab * d * 2 * (1 if cfg.tie_embeddings else 2)
    return float(q_bytes + embed_bytes) / 1e9


def make_rules(cfg, mesh: Mesh, shape_kind: str = "train",
               budget_gb: float = 8.0, force_2d: bool | None = None,
               seq_shard_cache: bool | None = None,
               seq_parallel: bool = False) -> ShardingPolicy:
    """Build weight+activation rules for (arch, mesh, shape kind)."""
    model_par = mesh.shape.get("model", 1)
    data_par = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)

    per_dev_1d = estimate_quantized_gb(cfg) / max(model_par, 1)
    use_2d = force_2d if force_2d is not None else per_dev_1d > budget_gb
    wrules = dict(_WEIGHT_AXES_2D if use_2d else _WEIGHT_AXES_1D)
    arules = dict(_ACT_AXES)

    # divisibility-driven head fallbacks: resolve_spec would drop these
    # anyway, but dropping them here keeps weights & activations consistent
    if cfg.moe is not None and cfg.moe.dispatch == "shard_map":
        # EP over every available axis (experts padded to divide); weights
        # must enter the program already laid out the way the shard_map body
        # splits them, or GSPMD would reshard per layer
        wrules["expert"] = ("pod", "data", "model")
        arules["expert"] = ("pod", "data", "model")
    if seq_parallel:
        # Megatron-style sequence parallelism: inter-layer activations shard
        # their sequence dim on 'model' (GSPMD turns the TP all-reduce into
        # reduce-scatter + all-gather and the remat carries shrink 16x)
        arules["seq"] = "model"
    if cfg.num_heads % model_par:
        arules["heads"] = None
        wrules["qkv_out"] = None if not use_2d else wrules["qkv_out"]
    if cfg.num_kv_heads % model_par:
        arules["kv_heads"] = None
        wrules["kv_out"] = None if not use_2d else wrules["kv_out"]

    if shape_kind in ("decode", "prefill"):
        # KV caches: kv_heads < TP everywhere at TP=16, so the cache shards
        # its sequence dim over 'model' (softmax/psum over the sharded dim is
        # GSPMD-native).  Long-context decode (batch < DP) additionally pulls
        # the idle ('pod','data') axes onto the sequence dim.
        if seq_shard_cache:
            arules["cache_seq"] = ("pod", "data", "model")
            arules["batch"] = None
            arules["tokens"] = None
        else:
            arules["cache_seq"] = "model"
    arules["__mesh__"] = mesh
    return ShardingPolicy(wrules, arules, [])


def resolve_spec(axes: tuple, shape: tuple, rules: dict, mesh: Mesh,
                 dropped: list | None = None) -> PartitionSpec:
    """Logical axes tuple + actual shape -> PartitionSpec (with fallbacks)."""
    spec, used = [], set()
    for dim, name in zip(shape, axes):
        rule = rules.get(name) if name is not None else None
        if rule is None:
            spec.append(None)
            continue
        mesh_axes = (rule,) if isinstance(rule, str) else tuple(rule)
        ok, size = [], 1
        for ax in mesh_axes:
            if ax in used or ax not in mesh.shape:
                continue
            ok.append(ax)
            size *= mesh.shape[ax]
        if ok and size > 1 and dim % size == 0:
            spec.append(tuple(ok) if len(ok) > 1 else ok[0])
            used.update(ok)
        else:
            if ok and dropped is not None:
                dropped.append((name, dim, tuple(ok)))
            spec.append(None)
    return PartitionSpec(*spec)


_AXES_LEAF = lambda x: isinstance(x, tuple) and all(  # noqa: E731
    isinstance(i, (str, type(None))) for i in x)


def tree_pspecs(axes_tree, value_tree, rules: dict, mesh,
                dropped: list | None = None):
    """Resolve a PartitionSpec tree matching ``value_tree`` from its logical
    axes tree.  Pure spec-level: ``mesh`` only needs a ``.shape`` mapping, so
    an ``AbstractMesh`` of the production shape works without any devices
    (how the sharding test-suite validates the full arch zoo on one CPU)."""
    import jax

    def one(axes, val):
        shape = val.shape if hasattr(val, "shape") else ()
        return resolve_spec(tuple(axes), tuple(shape), rules, mesh, dropped)

    return jax.tree.map(one, axes_tree, value_tree, is_leaf=_AXES_LEAF)


def tree_shardings(axes_tree, value_tree, rules: dict, mesh: Mesh,
                   dropped: list | None = None):
    """Build a NamedSharding tree matching value_tree from its axes tree."""
    import jax

    specs = tree_pspecs(axes_tree, value_tree, rules, mesh, dropped)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, PartitionSpec))
