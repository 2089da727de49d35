"""Program compiles the dispatcher counted inside the traced window
(`collect_dispatch` deltas of the window's sweeps).  Set-up warms every
shape, so this reads 0."""


def read(ctx):
    return ctx["compiles"]
