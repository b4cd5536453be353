"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--quick`` runs reduced
sweeps (used by CI); the full run reproduces every figure's data.
``--json PATH`` additionally writes all rows (plus total wall time per
figure) to a JSON file — CI uploads these as ``BENCH_*.json`` artifacts.
``--compare BASELINE.json`` diffs the current run against a committed
baseline (per-cell us_per_call ratios, printed and written to
``BENCH_compare.json``) so every run is anchored to the repo's perf
trajectory instead of an empty void.
"""
import argparse
import json
import sys
import time


def compare_records(current: dict, baseline: dict) -> list[dict]:
    """Per-cell ratio of current vs baseline us_per_call (matched by row
    name across all figures; cells present on only one side are skipped)."""
    def rows_by_name(rec):
        out = {}
        for fig in rec.get("figures", {}).values():
            for r in fig.get("rows", []):
                out[r["name"]] = r["us_per_call"]
        return out

    cur, base = rows_by_name(current), rows_by_name(baseline)
    diffs = []
    for name in sorted(cur.keys() & base.keys()):
        b = base[name]
        diffs.append({"name": name, "us_per_call": cur[name],
                      "baseline_us": b,
                      "ratio": round(cur[name] / b, 3) if b else None})
    return diffs


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true")
    p.add_argument("--only", default="",
                   help="comma-separated figure names (fig4,fig56,...)")
    p.add_argument("--json", default="",
                   help="write results to this JSON file (CI artifact)")
    p.add_argument("--compare", default="",
                   help="baseline JSON (e.g. BENCH_baseline.json) to diff "
                        "against; ratios go to stdout + BENCH_compare.json")
    args = p.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (fig1c_eviction, fig4_throughput, fig56_latency,
                            fig7_psf, fig9_overhead, fig10_car,
                            fig11_hotness, fig_faults, fig_prefetch,
                            fig_shard, kvdecode, roofline)

    figures = {
        "fig1c": fig1c_eviction.run,
        "fig4": fig4_throughput.run,
        "fig56": fig56_latency.run,
        "fig7": fig7_psf.run,
        "fig9": fig9_overhead.run,
        "fig10": fig10_car.run,
        "fig11": fig11_hotness.run,
        "fig_faults": fig_faults.run,
        "fig_prefetch": fig_prefetch.run,
        "fig_shard": fig_shard.run,
        "kvdecode": kvdecode.run,
        "roofline": roofline.run,
    }
    only = set(args.only.split(",")) if args.only else None
    record = {"quick": args.quick, "figures": {}}
    print("name,us_per_call,derived")
    for name, fn in figures.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn(quick=args.quick)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}", file=sys.stderr)
            raise
        wall = time.time() - t0
        record["figures"][name] = {
            "wall_s": round(wall, 2),
            "rows": [{"name": r[0], "us_per_call": r[1], "derived": r[2]}
                     for r in (rows or [])],
        }
        print(f"# {name} done in {wall:.1f}s", file=sys.stderr)

    if args.compare:
        with open(args.compare) as f:
            baseline = json.load(f)
        diffs = compare_records(record, baseline)
        print("compare_name,us_per_call,baseline_us,ratio")
        for d in diffs:
            print(f"{d['name']},{d['us_per_call']:.1f},"
                  f"{d['baseline_us']:.1f},{d['ratio']}")
        with open("BENCH_compare.json", "w") as f:
            json.dump({"baseline": args.compare, "cells": diffs}, f, indent=1)
        print("# wrote BENCH_compare.json", file=sys.stderr)
        record["compare"] = diffs

    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
