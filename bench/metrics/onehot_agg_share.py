"""Share of the flow-slots the device scan computes whose sparse
host-port sums (and, on ECMP rows, link-load sums) are one-hot
contractions on the MXU rather than scatter-adds, in %: 100 ×
`onehot_agg_flow_slots` ÷ `flow_slots_launched` over every launch of
the run, from the program's flight-recorder counters
(`repro.netsim.flight.dispatch_counts`, counted at each megabatch
launch).  A program without the `onehot_agg_flow_slots` counter reads
nothing."""


def read(ctx):
    try:
        from repro.netsim.flight import dispatch_counts
    except ImportError:
        return None
    c = dispatch_counts()
    if "onehot_agg_flow_slots" not in c or not c.get("flow_slots_launched"):
        return None
    return 100.0 * c["onehot_agg_flow_slots"] / c["flow_slots_launched"]
