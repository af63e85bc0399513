"""Attention kernels: roofline share of paged decode attention; required
bytes are each live slot's int8 context (codes and scales) read once per
step."""
from chipbench.layer_metrics._roofline import share

FAMILY = "attn_decode"
OPS = ("attn_decode_gqa_paged_pallas",)


def read(ctx):
    return share(ctx, OPS, ("attn.decode",))
