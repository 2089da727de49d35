"""JAX backend for the fluid network simulator.

A pure-functional twin of `netsim.sim.run_sim`: the per-slot dynamics run
as a jitted `lax.scan`, and whole sweep grids run as one `jax.vmap` batch
instead of a process pool — the megabatch path (`megabatch.py`) fuses an
entire routing x nic x fault x seed grid into a single launch that
compiles once, with per-element `StackIdx` branch selection inside the
traced program.  Fault schedules are compiled to dense per-slot capacity
timelines (`events.py`) because Python event callbacks cannot execute
inside `scan` — only `FaultSpec`-declared schedules are supported, not
arbitrary event closures.

Parity: with x64 enabled (`JAX_ENABLE_X64=1` or
`with jax.enable_x64(True):`), results match the NumPy backend within
1e-5 on every registry scenario (see `tests/test_jx_parity.py`).
"""
from .events import FaultTimeline, compile_fault_timeline, has_static_timeline
from .engine import (JxConfig, JxSimResult, StackIdx, dispatch_stats,
                     reset_dispatch_stats, run_compiled,
                     run_compiled_batch)
from .megabatch import (dispatch_megabatch, dispatch_planned,
                        finalize_group, plan_megabatch, run_megabatch)
from .state import FlowBatch, NicCarry, SimCarry

__all__ = [
    "FaultTimeline", "compile_fault_timeline", "has_static_timeline",
    "JxConfig", "JxSimResult", "StackIdx", "run_compiled",
    "run_compiled_batch", "run_megabatch", "dispatch_megabatch",
    "plan_megabatch", "dispatch_planned", "finalize_group",
    "dispatch_stats", "reset_dispatch_stats",
    "FlowBatch", "NicCarry", "SimCarry",
]
