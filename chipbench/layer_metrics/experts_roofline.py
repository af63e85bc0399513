"""LoRDS kernels: roofline share of the grouped expert kernel
(``lords_experts_pallas``), prefill and decode phases each bound by the
larger of FLOPs / peak and bytes / bandwidth; the bytes are each hit
expert's packed codes once a step (the engine's ``experts_hit``) plus the
live rows' activations."""
from chipbench.layer_metrics._roofline import share

FAMILY = "experts"
OPS = ("lords_experts_pallas",)


def read(ctx):
    return share(ctx, OPS, ("experts.prefill", "experts.decode"))
