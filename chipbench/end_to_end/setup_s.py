"""Set-up: weights drawn, program built, the cell's shapes warmed (and, in
a run that compiles, compiled)."""


def read(ctx):
    return ctx.setup_s
