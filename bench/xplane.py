"""Reduce a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read.

Device planes are named `/device:TPU:<n>`; their `XLA Ops` line holds
one event per executed HLO op, and each event's name is the op's HLO
text (`%fusion.7 = f32[8192]{...} fusion(s32[262144]{...} %a, ...),
kind=...`).  Host spans are the `bench.*` annotations of `spans.py`, on
any host plane; the profiler puts both on one clock.

  busy      union of the op intervals of each device inside the traced
            window, averaged over the devices (a `while` op counts as
            busy for its whole span: the device runs the loop)
  ops       device time per op label, summed over devices, loop and
            call containers left out
  pallas    device time of the Pallas kernels, per kernel
  scatter   device time of scatter ops
  spans     host time per `bench.*` span name
  idle      idle stretches of device 0, each labelled with the innermost
            host span that covers its midpoint, summed by label

The program does not name its kernels in the HLO (a Pallas call shows as
`custom_call_target="tpu_custom_call"` of an anonymous `closed_call`),
so `kernel_of` tells the engine's six kernels apart by their operand and
result counts and shapes.  Scatter-adds that XLA fused show as a
`fusion` that reads an integer index and a float value of the same
length and writes fewer elements than that (`is_scatter`).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
CONTAINERS = ("while", "conditional", "call")
_SHAPE = re.compile(r"\b(pred|bf16|[fsuc]\d+)\[([\d,]*)\]")
_OPCODE = re.compile(r"[)}\]] ([a-z][a-z\-]*)\(")


def _shapes(text: str) -> List[Tuple[str, int, Tuple[int, ...]]]:
    out = []
    for dt, dims in _SHAPE.findall(text):
        d = tuple(int(x) for x in dims.split(",") if x)
        n = 1
        for x in d:
            n *= x
        out.append((dt, n, d))
    return out


def parse_op(text: str):
    """`(name, opcode, results, operands)` of one HLO op's text; shapes
    are `(dtype, elements, dims)`."""
    name, _, rhs = text.partition(" = ")
    m = _OPCODE.search(rhs)
    if m is None:
        return name.lstrip("%"), "", _shapes(rhs), []
    depth, end = 1, len(rhs)
    for i in range(m.end(), len(rhs)):
        if rhs[i] == "(":
            depth += 1
        elif rhs[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return (name.lstrip("%"), m.group(1), _shapes(rhs[:m.start() + 1]),
            _shapes(rhs[m.end():end]))


def kernel_of(text: str) -> Optional[str]:
    """The engine's Pallas kernel an op is, or None.  Told apart by
    (operands, results): plane split (rate, eligibility, demand) -> 1,
    JSQ split (queue, capacity, weight) -> 1, bottleneck 2 -> 1, fused
    bucket load 2 -> 2, queue update 3 -> 2, NIC update 4 -> 4."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    _, _, res, ops = parse_op(text)
    arity = (len(ops), len(res))
    if arity == (3, 1):
        # the plane split's demand operand is a column, (..., 1)
        col = any(d and d[-1] == 1 for _, _, d in ops)
        return "_plane_split_kernel" if col else "_pair_score_kernel"
    return {(2, 1): "_bottleneck_kernel",
            (2, 2): "_load_bottleneck_kernel",
            (3, 2): "_queue_update_kernel",
            (4, 4): "_nic_update_kernel"}.get(arity, "other_kernel")


def is_scatter(text: str) -> bool:
    _, opcode, res, ops = parse_op(text)
    if opcode == "scatter":
        return True
    if opcode != "fusion":
        return False
    idx = {n for dt, n, _ in ops if dt[0] in "su"}
    vals = {n for dt, n, _ in ops if dt[0] in "fb"}
    out = sum(n for _, n, _ in res)
    return any(n in vals and out < n for n in idx)


def label(text: str) -> str:
    """Short name of an op for the breakdown."""
    name, opcode, res, _ = parse_op(text)
    kind = kernel_of(text) or ("scatter" if is_scatter(text) else opcode)
    shape = ",".join(f"{dt}[{','.join(map(str, d))}]" for dt, _, d in res)
    return f"{name} {kind} {shape}"


def union_ns(intervals) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _merged(intervals) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def read_planes(path: str):
    """(device_ops, host_spans) from an `.xplane.pb`: device_ops maps a
    device plane name to `[(start, end, text)]`, host_spans is
    `[(start, end, name)]` for every `bench.*` annotation."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List] = {}
    spans: List[Tuple[int, int, str]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            device_ops[plane.name] = [
                (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                 ev.name)
                for line in plane.lines if line.name == OPS_LINE
                for ev in line.events]
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns),
                                      ev.name))
    return device_ops, spans


def reduce(device_ops: Dict[str, List], spans: List[Tuple[int, int, str]],
           window: Optional[Tuple[int, int]] = None,
           sweep_span: str = "bench.sweep", n_top: int = 10) -> Dict:
    """Reduce the events of one trace.  `window` defaults to the first
    start and last end of the `sweep_span` spans: the traced sweeps."""
    if window is None:
        sw = [(s, e) for s, e, n in spans if n == sweep_span]
        if not sw:
            return {}
        window = (min(s for s, _ in sw), max(e for _, e in sw))
    w0, w1 = window
    busy, n_events = [], 0
    ops, pallas, scatter = defaultdict(int), defaultdict(int), 0
    classes: Dict[str, Tuple[Optional[str], bool, str, str]] = {}
    first = None
    for plane in sorted(device_ops):
        clipped = [(max(s, w0), min(e, w1), t)
                   for s, e, t in device_ops[plane] if e > w0 and s < w1]
        n_events += len(clipped)
        busy.append(union_ns((s, e) for s, e, _ in clipped))
        for s, e, t in clipped:
            c = classes.get(t)
            if c is None:
                c = classes[t] = (kernel_of(t), is_scatter(t),
                                  parse_op(t)[1], label(t))
            kern, scat, opcode, lab = c
            if opcode not in CONTAINERS:
                ops[lab] += e - s
            if kern:
                pallas[kern] += e - s
            elif scat:
                scatter += e - s
        if first is None:
            first = clipped
    span_ns = defaultdict(int)
    for s, e, n in spans:
        if e > w0 and s < w1:
            span_ns[n] += min(e, w1) - max(s, w0)
    gaps = []
    if first is not None:
        merged = _merged([(s, e) for s, e, _ in first])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _label_gap(spans, (a + b) // 2,
                                               sweep_span)))
    idle = defaultdict(int)
    for d, lab in gaps:
        idle[lab] += d
    n_dev = max(len(device_ops), 1)
    return {
        "window_ns": w1 - w0,
        "devices": len(device_ops),
        "op_events": n_events,
        "busy_ns": sum(busy) / n_dev if busy else 0.0,
        "pallas_ns": dict(pallas),
        "scatter_ns": scatter,
        "spans_ns": dict(span_ns),
        "top_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:n_top],
        "idle_by_span": sorted(idle.items(), key=lambda kv: -kv[1])[:n_top],
    }


def _label_gap(spans, t: int, sweep_span: str) -> str:
    """The innermost (shortest) `bench.*` span covering time `t`."""
    best = None
    for s, e, n in spans:
        if s <= t < e and (best is None or e - s < best[0]):
            best = (e - s, n)
    if best is None:
        return "outside any span"
    return best[1] if best[1] != sweep_span else "sweep, no inner span"
