"""Self-checks of the benchmark: ``python -m pytest chipbench/tests``.

They run on the CPU (four simulated devices for the sharded cell) with
interpret-mode kernels, before JAX is imported anywhere.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def checkout(tmp_path, configs=(), workloads=()):
    """A copy of the benchmark in ``tmp_path`` whose ``BENCHMARK.json``
    also holds ``configs`` and ``workloads``: new cells the way a later
    change adds them, as entries beside files."""
    import json
    import shutil
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] += list(configs)
    spec["workloads"] += list(workloads)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path

