"""Share of device busy time spent in XLA scatter ops (the sparse
segment sums of `link_load.segment_load`), in %."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["busy_ns"] or not tr["scatter_ns"]:
        return None
    return 100.0 * tr["scatter_ns"] / tr["devices"] / tr["busy_ns"]
