"""Device: model FLOP utilization, the model FLOPs the live tokens require
(quantized linears, attention over the live context, the head for each
served token; nothing for dead rows or dequantization) over
window x bf16 peak, in %."""


def read(ctx):
    o, p = ctx.obs, ctx.peaks
    flops = o["work"]["model"].flops
    if p is None or not flops or o["window_s"] <= 0:
        return None
    return 100.0 * flops / (o["window_s"] * p.bf16_flops)
