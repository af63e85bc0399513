"""Step plans: host-clock milliseconds per decode step (each burst ends in
a token fetch), the engine's decode_ms / decode_steps."""


def read(ctx):
    st = ctx.obs["stats"]
    return st["decode_ms"] / st["decode_steps"] if st["decode_steps"] else None
