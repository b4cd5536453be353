"""Chip smoke: the hybrid far-memory KV store served on TPU at real size.

One chip (the default):

    python chip_smoke.py [--seed N]

builds a store of 1,048,576 records of 1 KiB (YCSB core record shape:
fieldcount=10 x fieldlength=100, held as 256 f32) with 8-record pages,
frames for a quarter of the data and a virtual page space three times the
data.  It serves the ``mcd_cl`` zipf-churn traffic at batch 64 through
``repro.launch.serve.serve_kv`` (the launcher's own path): 256 ticks of
the hybrid plane with evacuation every 64 ticks and an epoch every 32, so
every main-path kernel runs, then 32 ticks each of the paging and object
planes.  Every served row is checked against the data on the host.

Four chips:

    python chip_smoke.py --four-chips [--seed N]

serves the sharded far tier (``EngineConfig(shards=4)`` on a ``far`` mesh)
with 1,048,576 records per shard at batch 256, checks every row and the
per-device memory balance, and compares the run's stats bit for bit with
the single-device vmap oracle at 65,536 records per shard.

The last stdout line is ``{"ok": true, "device": {...}}``.  The script
exits non-zero and prints no such line when JAX finds no TPU, when the
kernels do not resolve to compiled Pallas, or when any check fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

RECORDS = 1 << 20           # records per chip
RECORD_F32 = 256            # 1 KiB records
LOCAL = 0.25                # frames for this share of the data pages
HYBRID_TICKS = 256
BASELINE_TICKS = 32
EVAC_EVERY = 64
EPOCH_EVERY = 32
BATCH = 64
SHARDS = 4
SHARD_BATCH = 256
SHARD_TICKS = 64
ORACLE_RECORDS = 1 << 16    # records per shard in the oracle comparison


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str):
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str):
    print(msg, flush=True)


def array_bytes(pcfg) -> dict:
    """Bytes of each plane-state array per chip (shapes only)."""
    import jax
    from repro.core import state as state_lib
    init = jax.ShapeDtypeStruct((pcfg.num_objs, pcfg.obj_dim), pcfg.dtype)
    tmpl = jax.eval_shape(lambda x: state_lib.create(pcfg, x), init)
    out = {k: int(v.size * v.dtype.itemsize)
           for k, v in tmpl._asdict().items() if k != "stats"}
    out["total"] = sum(out.values())
    return out


def serve_plane(plane: str, ticks: int, pcfg, data, seed: int, *,
                mesh=None, batch: int = BATCH, **ecfg_kw):
    """One plane through the launcher's serve path; every row verified."""
    from repro.data import kvworkload
    from repro.launch.serve import serve_kv
    from repro.serving.engine import EngineConfig
    ecfg = EngineConfig(plane=plane, batch=batch, **ecfg_kw)
    wl = kvworkload.WORKLOADS["mcd_cl"](pcfg.num_objs, batch, ticks,
                                        seed=seed)
    return serve_kv(ecfg, pcfg, data, wl, mesh=mesh)


def report(plane: str, ticks: int, rep: dict):
    log(f"[{plane}] setup_s={rep['setup_s']:.3f} ticks={ticks} "
        f"requests={rep['requests']} mismatches={rep['mismatches']}")
    log(f"[{plane}] stats={json.dumps(rep['stats'], sort_keys=True)}")
    log(f"[{plane}] counters={json.dumps(rep['counters'], sort_keys=True)} "
        f"paging_fraction={rep['paging_fraction']:.4f} "
        f"latency_us={json.dumps(rep['latency'])}")
    check(rep["mismatches"] == 0,
          f"{plane}: {rep['mismatches']} served rows differ from the data")


def one_chip(seed: int):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.launch.serve import kv_plane_config, make_records

    pcfg = kv_plane_config(RECORDS, RECORD_F32, LOCAL)
    impl = ops.resolve_impl(pcfg.kernel_impl)
    log(f"kernel_impl={pcfg.kernel_impl} resolves to {impl}")
    check(impl == "pallas", f"kernels resolve to {impl!r}, not 'pallas'")
    log(f"geometry: num_objs={pcfg.num_objs} obj_dim={pcfg.obj_dim} "
        f"({pcfg.row_bytes} B records) page_objs={pcfg.page_objs} "
        f"num_vpages={pcfg.num_vpages} num_frames={pcfg.num_frames} "
        f"batch={BATCH}")
    log(f"bytes per array: {json.dumps(array_bytes(pcfg))}")

    t0 = time.time()
    data = make_records(RECORDS, RECORD_F32, seed)
    log(f"data: {data.nbytes} bytes generated from seed {seed} in "
        f"{time.time() - t0:.3f}s")

    rep, eng = serve_plane("hybrid", HYBRID_TICKS, pcfg, data, seed,
                           evac_every=EVAC_EVERY, epoch_every=EPOCH_EVERY)
    report("hybrid", HYBRID_TICKS, rep)
    check(rep["requests"] == HYBRID_TICKS * BATCH, "hybrid: requests lost")
    check(rep["counters"]["evac_calls"] >= HYBRID_TICKS // EVAC_EVERY,
          "hybrid: too few evacuations")
    check(rep["stats"]["epochs"] >= HYBRID_TICKS // EPOCH_EVERY,
          "hybrid: too few epochs")
    ids = jnp.zeros((BATCH,), jnp.int32)
    text = eng._exec.lower(eng.state, ids, eng._plan(eng.state, ids)
                           ).compile().as_text()
    has_kernel = "tpu_custom_call" in text
    log(f"execute program contains tpu_custom_call: {has_kernel}")
    check(has_kernel, "the compiled execute program has no Pallas kernel")
    del eng, text
    gc.collect()

    for plane in ("paging", "object"):
        rep, eng = serve_plane(plane, BASELINE_TICKS, pcfg, data, seed)
        report(plane, BASELINE_TICKS, rep)
        check(rep["requests"] == BASELINE_TICKS * BATCH,
              f"{plane}: requests lost")
        del eng
        gc.collect()
    stats = jax.devices()[0].memory_stats()
    log(f"peak_bytes_in_use={stats['peak_bytes_in_use']} "
        f"bytes_limit={stats['bytes_limit']}")


def four_chips(seed: int):
    import jax
    from repro.core import shardplane
    from repro.launch import mesh as mesh_lib
    from repro.launch.serve import kv_plane_config, make_records

    check(len(jax.devices()) >= SHARDS, f"needs {SHARDS} chips")
    mesh = mesh_lib.make_far_mesh(SHARDS)
    kw = dict(batch=SHARD_BATCH, shards=SHARDS, evac_every=EVAC_EVERY,
              epoch_every=EPOCH_EVERY)

    pcfg = kv_plane_config(SHARDS * RECORDS, RECORD_F32, LOCAL)
    log(f"geometry: shards={SHARDS} records_per_shard={RECORDS} "
        f"obj_dim={pcfg.obj_dim} num_vpages={pcfg.num_vpages} "
        f"num_frames={pcfg.num_frames} batch={SHARD_BATCH}")
    log(f"bytes per array per chip: "
        f"{json.dumps(array_bytes(shardplane.shard_config(pcfg, SHARDS)))}")
    data = make_records(SHARDS * RECORDS, RECORD_F32, seed)
    rep, eng = serve_plane("hybrid", SHARD_TICKS, pcfg, data, seed,
                           mesh=mesh, **kw)
    report("sharded", SHARD_TICKS, rep)
    check(rep["requests"] == SHARD_TICKS * SHARD_BATCH,
          "sharded: requests lost")
    used = [d.memory_stats()["bytes_in_use"] for d in mesh.devices.flat]
    mean = sum(used) / len(used)
    log(f"bytes_in_use per device={used} max/mean={max(used) / mean:.4f}")
    check(max(used) <= 1.5 * mean, "sharded state is not balanced")
    del eng, data
    gc.collect()

    pcfg = kv_plane_config(SHARDS * ORACLE_RECORDS, RECORD_F32, LOCAL)
    data = make_records(SHARDS * ORACLE_RECORDS, RECORD_F32, seed)
    reps = {}
    for name, m in (("mesh", mesh), ("oracle", None)):
        reps[name], eng = serve_plane("hybrid", SHARD_TICKS, pcfg, data,
                                      seed, mesh=m, **kw)
        report(f"{name}@{ORACLE_RECORDS}", SHARD_TICKS, reps[name])
        del eng
    same = reps["mesh"]["stats"] == reps["oracle"]["stats"]
    log(f"mesh stats == vmap oracle stats: {same}")
    check(same, "the sharded run's stats differ from the vmap oracle")
    peaks = [d.memory_stats()["peak_bytes_in_use"]
             for d in mesh.devices.flat]
    log(f"peak_bytes_in_use per device={peaks}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--four-chips", action="store_true",
                   help="run the sharded far tier on four chips instead")
    args = p.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={dev.platform})",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    log(f"device: {dev.device_kind} x{len(jax.devices())}")
    t0 = time.time()
    (four_chips if args.four_chips else one_chip)(args.seed)
    log(f"wall_s={time.time() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
