"""Share of the flow-slots the device scan computes whose ECMP link
lookups are one-hot contractions on the MXU rather than point gathers,
in %: 100 × `ecmp_onehot_flow_slots` ÷ `flow_slots_launched` over every
launch of the run, from the program's flight-recorder counters
(`repro.netsim.flight.dispatch_counts`, counted at each megabatch
launch).  A program without the `ecmp_onehot_flow_slots` counter reads
nothing."""


def read(ctx):
    try:
        from repro.netsim.flight import dispatch_counts
    except ImportError:
        return None
    c = dispatch_counts()
    if "ecmp_onehot_flow_slots" not in c or not c.get("flow_slots_launched"):
        return None
    return 100.0 * c["ecmp_onehot_flow_slots"] / c["flow_slots_launched"]
