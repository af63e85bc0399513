"""Attention kernels: roofline share of paged latent-attention decode
(absorbed form); required bytes are each live slot's int8 latents, their
scales and the bf16 rope keys read once per step."""
from chipbench.layer_metrics._roofline import share

FAMILY = "attn_decode_mla"
OPS = ("attn_decode_mla_paged_pallas",)


def read(ctx):
    return share(ctx, OPS, ("attn.decode",))
