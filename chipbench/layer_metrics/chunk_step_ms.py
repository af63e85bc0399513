"""Step plans: host-clock milliseconds per chunk-prefill step, the mean
duration of the engine's ``engine.step`` spans of plan ``chunk`` (each from
the launch to the fetch of the tokens it samples)."""


def read(ctx):
    rec = ctx.obs["stats"].get("spans", {}).get("engine.step.chunk")
    return rec["ms"] / rec["count"] if rec and rec["count"] else None
