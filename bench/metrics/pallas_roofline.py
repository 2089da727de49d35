"""Share of the memory roofline the engine's Pallas kernels reach, in %:
the bytes their stages must move in the traced sweeps
(`kernel_bytes.py`) over the chip's HBM bandwidth (`peaks.json`),
divided by the kernels' device time in the trace.  Only kernels that
ran count, on both sides."""
import json
import os

from kernel_bytes import sweep_bytes


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["pallas_ns"]:
        return None
    with open(os.path.join(ctx["bench_dir"], "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if ctx["device_kind"] not in peaks:
        raise KeyError(f"no peak for device kind {ctx['device_kind']!r} "
                       "in bench/peaks.json")
    bw = peaks[ctx["device_kind"]]["hbm_bytes_per_s"]
    ran = tr["pallas_ns"]
    need = sweep_bytes(ctx["traced_points"],
                       dense_ecmp="_load_bottleneck_kernel" in ran)
    moved = sum(b for k, b in need.items() if k in ran)
    return 100.0 * (moved / bw) / (sum(ran.values()) / 1e9)
