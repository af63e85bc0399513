"""Shared arithmetic of the kernel roofline readers: the least time the
chip needs for the required work of the kernel's phases, over the device
time of the kernel's ops in the trace, in %."""
from chipbench.work import least_seconds


def share(ctx, ops, phases):
    t, p = ctx.trace, ctx.peaks
    if t is None or p is None:
        return None
    dev = t.seconds_matching(ops)
    if dev <= 0:
        return None
    w = ctx.obs["work"]
    least = sum(least_seconds(w[ph], p.bf16_flops, p.hbm_bytes_per_s)
                for ph in phases)
    return 100.0 * least / dev if least > 0 else None
