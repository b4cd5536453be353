"""A later cell, configuration, traffic mix and metric are new files and
new entries: the harness finds them by name with no file of it edited."""
import json
import os
from types import SimpleNamespace

import bench
import rehearse
from conftest import ROOT, checkout


def _snapshot(d):
    out = {}
    for dirpath, _, files in os.walk(d):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, d)] = fh.read()
    return out


def test_new_config_traffic_metric_found_by_name(tmp_path):
    root = checkout(tmp_path)
    before = _snapshot(root / "chipbench")
    home = root / "chipbench"
    cfg = json.loads((home / "configs" / "ycsb_c.json").read_text())
    cfg.update(name="ycsb_c_half", store=dict(cfg["store"], local_share=0.5))
    (home / "configs" / "ycsb_c_half.json").write_text(json.dumps(cfg))
    (home / "traffic" / "poisson_40.json").write_text(json.dumps(
        {"arrival": "open", "rate_per_s": 40}))
    (home / "metrics" / "ids_per_tick.py").write_text(
        "def read(rec):\n    return rec.ids / rec.ticks if rec.ticks "
        "else None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "ycsb_c_half", "source": "x",
                            "file": "chipbench/configs/ycsb_c_half.json",
                            "reduced": [], "why": "half local"})
    spec["workloads"].append({"name": "ycsb_c_half.slow",
                              "config": "ycsb_c_half",
                              "traffic": "poisson_40", "chips": 1,
                              "why": "new cell"})
    spec["per_layer"].append({"name": "ids_per_tick", "unit": "ids",
                              "better": "higher",
                              "source": "program_counter", "layer": "engine",
                              "moves": "goodput_rps",
                              "workloads": ["ycsb_c_half.slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    s = bench.Spec.load(str(root))
    assert s.config("ycsb_c_half")["store"]["local_share"] == 0.5
    assert s.traffic("poisson_40")["rate_per_s"] == 40
    names = [m["name"] for m in s.metrics("ycsb_c_half.slow", True)]
    assert "ids_per_tick" in names and "device_idle_share" not in names
    assert s.reader("ids_per_tick")(SimpleNamespace(ids=640, ticks=10)) == 64
    assert "ids_per_tick" not in [m["name"]
                                  for m in s.metrics("ycsb_c.closed", True)]

    out = rehearse.rehearse("ycsb_c_half.slow", seed=2**31 + 1, seconds=1.0,
                            root=str(root))
    assert out["correct"] and out["attempted"] == 40
    after = _snapshot(home)
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_device_kind_is_refused():
    spec = bench.Spec.load(ROOT)
    try:
        spec.peaks("TPU v9 imaginary")
    except bench.Refused:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_no_chip_is_refused():
    # the CPU here is no accelerator: a real run must refuse, not fall back
    spec = bench.Spec.load(ROOT)
    try:
        bench.run(spec, "ycsb_c.closed", 1, 1.0, False, 0.0)
    except bench.Refused:
        return
    raise AssertionError("a run without a TPU must be refused")
