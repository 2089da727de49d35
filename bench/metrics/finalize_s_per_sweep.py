"""Host seconds per traced sweep in the finalize layer: the
`bench.finalize_group` spans (which include the wait for the device's
results) and the `bench.distill_metrics` spans, summed."""
from spans import LAYERS


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx["sweeps"]:
        return None
    ns = sum(v for k, v in tr["spans_ns"].items()
             if LAYERS.get(k) == "finalize")
    return ns / 1e9 / ctx["sweeps"] if ns else None
