"""Share of the traced window in which no op ran on the device, in %."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["window_ns"] or not tr["op_events"]:
        return None
    return 100.0 * (1.0 - tr["busy_ns"] / tr["window_ns"])
