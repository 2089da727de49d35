"""Device busy nanoseconds per simulated flow-slot: the union of the
device's op intervals in the traced window, averaged over chips and
summed back over them, over the window's flow-slots."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["busy_ns"] or not ctx["flow_slots"]:
        return None
    return tr["busy_ns"] * tr["devices"] / ctx["flow_slots"]
