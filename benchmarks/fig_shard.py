"""Sharded far tier scaling sweep: shards = 1/2/4/8 x {hybrid, paging}.

Serves the MCD-CL (zipf+churn) workload through the serving engine at each
shard count and reports unpaced drain throughput (batches/s) plus p99
request latency.  ``shards=1`` is the plain single-device engine — the
baseline every sharded cell is anchored to (it must sit within noise of
the pre-sharding engine, since the sharded path only engages at
``shards>1``); ``shards>1`` runs the round-based all_to_all exchange of
``repro.core.shardplane`` under shard_map on a ``far`` mesh.

Two exchange schedules are swept for the hybrid sharded cells:

* ``fig_shard/hybrid/s{N}`` — the default **overlap** schedule (fused
  2-collective rounds, round r+1's ingress issued before round r's
  return rows are collected).
* ``fig_shard/hybrid/s{N}/serial`` — the legacy **serial** schedule
  (3 collectives per round, each round fully retired before the next
  packs).  Comparing the two cells at equal shards is the headline
  overlap-vs-serial throughput number; both produce bit-identical
  results (tests/test_sharded.py holds that line).

Hybrid sharded overlap cells also carry a subtractive per-phase wall
breakdown: ``pack_pct`` times just the per-round pack chain
(``shardplane.jitted_phase_probe(cfg, "pack")``), ``coll_pct`` is the
ingress collective's share (probe "ingress" minus probe "pack"), and
``serve_pct`` is the remainder of the full access step — serve + egress
collective + collect.  The decomposition is approximate (phases overlap
by construction, and XLA fuses across them differently in isolation) but
tracks where wall time goes as shards scale.

The sweep runs in this process over the devices JAX sees: shard counts
above ``len(jax.devices())`` are left out.  For a CPU rehearsal, give the
host simulated devices on the command line before JAX starts, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8 python
benchmarks/run.py --only fig_shard``.

NOTE: on CPU the shard_map cells pay real collective overhead for
simulated parallelism (all 8 "devices" share the same cores), so
``batches/s`` here measures exchange + dispatch cost, not the bandwidth
scaling a real multi-chip far tier buys — and the overlap schedule's win
is understated, since simulated devices cannot actually run a collective
and a serve concurrently.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import shardplane
from repro.data import kvworkload
from repro.launch import mesh as mesh_lib
from repro.serving.engine import Engine, EngineConfig

from .common import emit, plane_config


def per_call_us(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / n * 1e6


def run(quick: bool = False):
    steps, batch = (30 if quick else 120), 64
    pcfg = plane_config(0.25)
    data = jnp.zeros((pcfg.num_objs, pcfg.obj_dim), pcfg.dtype)
    shard_counts = [s for s in (1, 2, 4, 8) if s <= len(jax.devices())]
    rows = []
    for plane in ["hybrid", "paging"]:
        for shards in shard_counts:
            # serial cells only where the exchange actually runs (hybrid,
            # shards>1); paging and s1 have no collective schedule to compare
            exchanges = ["overlap"]
            if plane == "hybrid" and shards > 1:
                exchanges.append("serial")
            for exch in exchanges:
                ecfg = EngineConfig(plane=plane, batch=batch, evac_every=16,
                                    shards=shards, shard_exchange=exch)
                mesh = mesh_lib.make_far_mesh(shards) if shards > 1 else None
                wl = list(kvworkload.zipf_churn(pcfg.num_objs, batch, steps,
                                                seed=3))
                # untimed warm run on a throwaway engine: drives every
                # lazily jitted path (evacuation, epoch advance, health
                # probe) far enough to compile, so the timed run measures
                # steady state instead of charging whichever cell compiles
                # first (the caches are keyed on config, which the timed
                # engine shares)
                Engine(ecfg, pcfg, data, mesh=mesh).run(iter(wl[:20]))
                eng = Engine(ecfg, pcfg, data, mesh=mesh)
                t0 = time.time()
                rep = eng.run(iter(wl))
                dt = time.time() - t0
                lat = rep["latency"]
                spills = rep["stats"].get("ingress_spills", 0)
                name = f"fig_shard/{plane}/s{shards}"
                if exch == "serial":
                    name += "/serial"
                derived = (f"tput_bps={steps / dt:.1f};"
                           f"p99_us={lat['p99_us']:.0f};"
                           f"p50_us={lat['p50_us']:.0f};"
                           f"paging_frac={rep['paging_fraction']:.2f};"
                           f"spills={spills}")
                if plane == "hybrid" and shards > 1 and exch == "overlap":
                    # subtractive phase breakdown on a warm representative
                    # batch: pack-only probe, pack+ingress probe, full access
                    S, R = shards, eng.scfg.shard_batch
                    ids2d = jnp.asarray(
                        np.asarray(wl[0]).reshape(S, R) % pcfg.num_objs,
                        jnp.int32)
                    t_pack = per_call_us(
                        shardplane.jitted_phase_probe(eng.scfg, "pack",
                                                      mesh), ids2d)
                    t_ing = per_call_us(
                        shardplane.jitted_phase_probe(eng.scfg, "ingress",
                                                      mesh), ids2d)
                    # the library's access program: the engine's own
                    # donates its input state, which this loop reuses
                    t_full = per_call_us(
                        shardplane.jitted_access(eng.scfg, eng.cfg.mode,
                                                 mesh), eng.state, ids2d)
                    pack = min(t_pack, t_full) / t_full
                    coll = min(max(t_ing - t_pack, 0.0), t_full) / t_full
                    serve = max(1.0 - pack - coll, 0.0)
                    derived += (f";pack_pct={100 * pack:.0f}"
                                f";coll_pct={100 * coll:.0f}"
                                f";serve_pct={100 * serve:.0f}")
                rows.append((name, dt / steps * 1e6, derived))
    emit(rows)
    return rows


if __name__ == "__main__":
    run()
