"""repro.models — the architecture zoo (all linears quantized via repro.core)."""
from repro.models.common import P, activation_rules, shard, split_tree  # noqa: F401
from repro.models.model import (  # noqa: F401
    cache_init,
    forward_decode,
    forward_decode_paged,
    forward_prefill,
    forward_prefill_chunk,
    forward_train,
    model_init,
    paged_cache_init,
)
from repro.models.moe import EXPERT_STATS  # noqa: F401
