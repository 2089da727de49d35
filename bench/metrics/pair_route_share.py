"""Share of the flow-slots the device scan computes that route by leaf
pair (adaptive routing, ar or war), in %: 100 × `pair_route_flow_slots`
÷ `flow_slots_launched` over every launch of the run, from the
program's flight-recorder counters (`repro.netsim.flight.dispatch_counts`,
counted at each megabatch launch from each row's own routing).  A
program without the `pair_route_flow_slots` counter reads nothing."""


def read(ctx):
    try:
        from repro.netsim.flight import dispatch_counts
    except ImportError:
        return None
    c = dispatch_counts()
    if "pair_route_flow_slots" not in c or not c.get("flow_slots_launched"):
        return None
    return 100.0 * c["pair_route_flow_slots"] / c["flow_slots_launched"]
