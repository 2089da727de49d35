"""Seconds the run spent in XLA compiles and persistent-cache loads of
its programs, from the program's flight-recorder counters
(`repro.netsim.flight.dispatch_counts`: `xla_compile_s` +
`cache_load_s`, which JAX's compile events feed).  Those are whole-
process totals, read after the window: they are set-up's only when the
window launched no program it had not launched before, so the reader
reads nothing unless the window's `compiles` is 0.  A program without
those counters reads nothing."""


def read(ctx):
    if ctx.get("compiles") != 0:
        return None
    try:
        from repro.netsim.flight import dispatch_counts
    except ImportError:
        return None
    c = dispatch_counts()
    if "xla_compile_s" not in c and "cache_load_s" not in c:
        return None
    return c.get("xla_compile_s", 0.0) + c.get("cache_load_s", 0.0)
