"""Share of the memory roofline the adaptive-routing scoring kernel
(`_pair_score_kernel`, the `pair_fractions` Pallas call) reaches, in %:
the bytes it must move in the traced sweeps (`kernel_bytes.py`) over
the chip's HBM bandwidth (`peaks.json`), divided by its device time in
the trace.  Reads nothing where the kernel did not run."""
import json
import os

from kernel_bytes import sweep_bytes

KERNEL = "_pair_score_kernel"


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["pallas_ns"].get(KERNEL):
        return None
    with open(os.path.join(ctx["bench_dir"], "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if ctx["device_kind"] not in peaks:
        raise KeyError(f"no peak for device kind {ctx['device_kind']!r} "
                       "in bench/peaks.json")
    bw = peaks[ctx["device_kind"]]["hbm_bytes_per_s"]
    need = sweep_bytes(ctx["traced_points"], dense_ecmp=False)
    return 100.0 * (need[KERNEL] / bw) / (tr["pallas_ns"][KERNEL] / 1e9)
