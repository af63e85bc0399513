"""Engine: host milliseconds of the engine's own code per step launch, the
self time of every ``engine.*`` span except the jitted call
(``engine.dispatch``) and the blocking fetch of its tokens
(``engine.fetch``), over the step launches (``engine.step.<plan>``)."""

LAUNCH = "engine.step."
DEVICE_BOUND = ("engine.dispatch", "engine.fetch")


def read(ctx):
    spans = ctx.obs["stats"].get("spans", {})
    launches = sum(r["count"] for k, r in spans.items()
                   if k.startswith(LAUNCH))
    if not launches:
        return None
    own = sum(r["self_ms"] for k, r in spans.items()
              if k not in DEVICE_BOUND)
    return own / launches
