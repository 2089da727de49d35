"""Simulated flow-slots per second of window wall time: every real flow
for every slot of every sweep that finished in the window, over the
window's length on the host clock."""


def read(ctx):
    return ctx["flow_slots"] / ctx["window_s"]
