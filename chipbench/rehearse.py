"""CPU rehearsal of a cell: tiny sizes, interpret-mode Pallas kernels.

Used only by the self-checks (``chipbench/tests``).  Run it with
``JAX_PLATFORMS=cpu``.  It drives the whole harness (store build, warm-up,
window, check) on a small copy of the cell's deployment, skipping the look
for a chip; its result carries counts and checks only, never a device
metric's name.
"""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

import bench  # noqa: E402

TINY = {"records_per_shard": 2048, "batch": 64, "kernel_impl": "interpret"}


def rehearse(workload: str, seed: int = 0, seconds: float = 1.0, *,
             root: str = ROOT, control=None) -> dict:
    t = time.perf_counter()
    spec = bench.Spec.load(root)
    return bench.run(spec, workload, seed, seconds, False, t,
                     control=control, rehearse=TINY)
