"""Host seconds per traced sweep in the host-prep layer: the
`bench.compile_scenario`, `bench.plan_megabatch` and
`bench.dispatch_planned` spans (`spans.py`), summed."""
from spans import LAYERS


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx["sweeps"]:
        return None
    ns = sum(v for k, v in tr["spans_ns"].items()
             if LAYERS.get(k) == "host_prep")
    return ns / 1e9 / ctx["sweeps"] if ns else None
