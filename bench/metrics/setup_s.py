"""Set-up seconds on the host clock: interpreter and TPU start-up,
building the specs and the warm-up sweeps (compiles included on a cold
cache)."""


def read(ctx):
    return ctx["setup_s"]
