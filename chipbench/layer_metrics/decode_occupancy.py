"""Engine: share of decode rows that carried a live token,
tokens generated by decode steps / (decode steps x slots), in %."""


def read(ctx):
    o = ctx.obs
    steps = o["stats"]["decode_steps"]
    if not steps:
        return None
    toks = sum(max(len(r["tokens"]) - 1, 0) for r in o["records"])
    return 100.0 * toks / (steps * o["slots"])
