"""Markdown report generator for EXPERIMENTS.md §Dry-run / §Roofline,
plus an observability section from an optional ResultSet's
flight-recorder stats.

  PYTHONPATH=src python -m benchmarks.report > experiments/report.md
  PYTHONPATH=src python -m benchmarks.report --resultset sweep.json
"""
from __future__ import annotations

import argparse

from .roofline import load_cells, roofline_row


def fmt_bytes(b) -> str:
    if not isinstance(b, (int, float)):
        return "-"
    return f"{b / (1 << 30):.2f}"


def print_observability(resultset_path: str | None = None) -> None:
    """A ResultSet JSON's flight-recorder executor stats, when one is
    given."""
    print("\n## Observability\n")
    if not resultset_path:
        print("- no ResultSet given (--resultset)")
        return
    from repro.experiments import ResultSet

    rs = ResultSet.from_json(open(resultset_path, encoding="utf-8").read())
    fl = rs.flight
    if not fl:
        print(f"- {resultset_path}: no flight-recorder data")
        return
    print(f"Flight recorder ({resultset_path}, "
          f"experiment {fl.get('experiment')!r}): "
          f"{fl.get('cache_hits', 0)} cache hits, "
          f"{fl.get('cache_misses', 0)} misses\n")
    print("| backend | mode | points | wall s | dispatch stats |")
    print("|---|---|---|---|---|")
    for ex in fl.get("executions", ()):
        stats = ex.get("dispatch_stats")
        print(f"| {ex.get('backend')} | {ex.get('mode')} | "
              f"{ex.get('n_points')} | {ex.get('wall_s', 0.0):.3f} | "
              f"{stats if stats else '-'} |")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--resultset", default=None,
                   help="ResultSet JSON whose flight-recorder stats "
                        "join the observability section")
    args = p.parse_args()
    cells = load_cells()
    print("## §Dry-run (per-device memory from the production compile)\n")
    print("| arch | shape | mesh | status | args GiB | temp GiB | "
          "compile s |")
    print("|---|---|---|---|---|---|---|")
    for c in sorted(cells, key=lambda x: (x["arch"], x["shape"],
                                          x["mesh"])):
        if c.get("skipped"):
            status = "SKIP (full-attn @500k)"
            print(f"| {c['arch']} | {c['shape']} | {c['mesh']} | {status} "
                  f"| - | - | - |")
            continue
        status = "OK" if c.get("ok") else "FAIL"
        mem = c.get("memory", {})
        print(f"| {c['arch']} | {c['shape']} | {c['mesh']} | {status} | "
              f"{fmt_bytes(mem.get('argument_size_in_bytes'))} | "
              f"{fmt_bytes(mem.get('temp_size_in_bytes'))} | "
              f"{c.get('compile_s', '-')} |")

    print("\n## §Roofline (single-pod 16x16; per-device terms, TPU v5e "
          "constants)\n")
    print("| arch | shape | compute ms | memory ms | collective ms | "
          "dominant | MODEL/HLO | roofline frac |")
    print("|---|---|---|---|---|---|---|---|")
    rows = [r for r in (roofline_row(c) for c in cells)
            if r and r["mesh"] == "single"]
    for r in sorted(rows, key=lambda x: (x["arch"], x["shape"])):
        print(f"| {r['arch']} | {r['shape']} | "
              f"{r['t_compute_s'] * 1e3:.2f} | "
              f"{r['t_memory_s'] * 1e3:.2f} | "
              f"{r['t_collective_s'] * 1e3:.2f} | {r['dominant']} | "
              f"{r['useful_ratio']:.2f} | "
              f"{r['roofline_fraction']:.3f} |")

    print("\n## Multi-pod pass/fail\n")
    multi = [c for c in cells if c["mesh"] == "multi"]
    ok = sum(1 for c in multi if c.get("ok") and not c.get("skipped"))
    skip = sum(1 for c in multi if c.get("skipped"))
    fail = [f"{c['arch']}/{c['shape']}" for c in multi
            if not c.get("ok") and not c.get("skipped")]
    print(f"- {ok} compiled, {skip} skipped (long_500k full-attention), "
          f"{len(fail)} failed {fail if fail else ''}")

    print_observability(resultset_path=args.resultset)


if __name__ == "__main__":
    main()
