"""Share of the flow-slots the device scan computes that belong to pad
flows, in %: 100 × (1 − real ÷ launched flow-slots) over every launch
of the run, from the program's flight-recorder counters
(`repro.netsim.flight.dispatch_counts`: `flow_slots_real` and
`flow_slots_launched`, counted at each megabatch launch).  Flow counts
are padded up to power-of-two buckets; this is the share of per-flow
device work spent on the padding.  A program without those counters
reads nothing."""


def read(ctx):
    try:
        from repro.netsim.flight import dispatch_counts
    except ImportError:
        return None
    c = dispatch_counts()
    if not c.get("flow_slots_launched"):
        return None
    return 100.0 * (1.0 - c["flow_slots_real"] / c["flow_slots_launched"])
