"""Run one benchmark cell once, on the chip it is started on.

    python3 chipbench/run.py --workload ycsb_c.closed --seed 7 --seconds 20 \
        --trace 0

The cell, its configuration, traffic and metrics are found by name from
``BENCHMARK.json`` (see ``bench.py``).  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` and, last, ``checks``: each number compared with the reference
beside its limit, which also end standard error.  With no TPU, or fewer
chips than the cell asks for, it prints no result and exits 2.

``--control bf16`` replaces the served rows with the reference computed in
bfloat16, one precision below the stored float32: the control, which must
come out not correct.  The benchmark's own runs never use it.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", choices=("bf16",), default=None)
    p.add_argument("--dump", default=None,
                   help="directory for the traced run's compact trace")
    args = p.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    import cache
    cache.enable(ROOT)
    import bench
    spec = bench.Spec.load(ROOT)
    try:
        out = bench.run(spec, args.workload, args.seed, args.seconds,
                        bool(args.trace), T_PROCESS, control=args.control,
                        dump=args.dump)
    except bench.Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
