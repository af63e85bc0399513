"""Offline batch throughput: every token generated in the window, the
partial output of requests the window cut included, over the whole
window (the engine's run, to the end of the step that closes it)."""


def read(ctx):
    o = ctx.obs
    toks = sum(len(r["tokens"]) for r in o["records"])
    return toks / o["window_s"] if toks else None
