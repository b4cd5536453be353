"""One-off knee sweep of a cell's deployment under open-loop load, on the
chip.

    python3 chipbench/sweep.py --workload ycsb_c.closed \
        --rates 8000,9000,10000 --seconds 10 --seed 1

For each rate it builds and warms the cell's store afresh, as a run does,
offers Poisson load for ``--seconds`` through the run's own loop (rows
copied to the host for the check, the collector off) and prints one JSON
line: goodput,
p50 and p99, and whether the backlog grew (the median latency of the last
quarter of arrivals against the first quarter's).  The knee is the highest rate
whose backlog does not grow; an open cell offers about 0.8 of it.
"""
import argparse
import gc
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    import numpy as np
    import cache
    cache.enable(ROOT)
    import bench
    import traffic as traffic_lib
    spec = bench.Spec.load(ROOT)
    cell = spec.workload(args.workload)
    try:
        bench.devices_for(cell["chips"], None)
    except bench.Refused as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    cfg = spec.config(cell["config"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        seed = args.seed + i
        t = time.perf_counter()
        eng = bench.build_engine(cfg, seed)
        bench.warm_up(eng, cfg, seed, cfg["warmup"])
        setup_s = time.perf_counter() - t
        drv = bench.Loader(eng, keep=True)
        src = traffic_lib.Requests(cfg, seed, 2)
        with bench.collector_off():
            w, t0, _ = bench.drive_open(drv, src, rate, args.seconds, seed)
        eng.drain()
        drv_ticks, drv_ids = drv.ticks, drv.ids_real
        del eng, drv
        gc.collect()
        ts, td = np.asarray(w.t_start), np.asarray(w.t_done)
        lat = (td - ts) * 1e3
        q = len(ts) // 4
        first, last = np.nanmedian(lat[:q]), np.nanmedian(lat[-q:])
        done = int(np.sum(td <= t0 + args.seconds))
        print(json.dumps({
            "rate": rate, "offered": len(ts), "setup_s": setup_s,
            "goodput_rps": done / args.seconds,
            "p50_ms": float(np.nanpercentile(lat, 50)),
            "p99_ms": float(np.nanpercentile(lat, 99)),
            "p50_first_quarter_ms": float(first),
            "p50_last_quarter_ms": float(last),
            "backlog_grows": bool(last > 1.5 * first),
            "ticks": drv_ticks, "ids_per_tick": drv_ids / max(drv_ticks, 1),
            "late_ms_p99": float(np.percentile(np.asarray(w.late) * 1e3, 99)),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
