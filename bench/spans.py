"""Host spans on the profiler's clock, taken from the benchmark's own
files: the module attributes that `execute_points` calls at run time are
wrapped in `jax.profiler.TraceAnnotation` for the traced window only.

Each span name is `bench.<function>`; `LAYERS` says which per-layer
metric reads it."""
from __future__ import annotations

import importlib
from contextlib import contextmanager
from functools import wraps

# (module, attribute, layer)
TARGETS = (
    ("repro.experiments.execute", "compile_scenario", "host_prep"),
    ("repro.netsim.jx.megabatch", "plan_megabatch", "host_prep"),
    ("repro.netsim.jx.megabatch", "dispatch_planned", "host_prep"),
    ("repro.netsim.jx.megabatch", "finalize_group", "finalize"),
    ("repro.experiments.execute", "distill_metrics", "finalize"),
)
LAYERS = {f"bench.{attr}": layer for _, attr, layer in TARGETS}
SWEEP = "bench.sweep"


def _annotated(fn, name):
    import jax

    @wraps(fn)
    def inner(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return inner


@contextmanager
def host_spans():
    """Wrap every target for the duration of the block, then put the
    program's own functions back."""
    saved = []
    try:
        for mod_name, attr, _ in TARGETS:
            mod = importlib.import_module(mod_name)
            fn = getattr(mod, attr)
            saved.append((mod, attr, fn))
            setattr(mod, attr, _annotated(fn, f"bench.{attr}"))
        yield
    finally:
        for mod, attr, fn in saved:
            setattr(mod, attr, fn)
