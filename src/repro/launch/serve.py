"""Serving launcher: batched far-memory KV serving through a chosen data
plane (the Memcached/WebService analogue), or LM token decoding through the
Atlas-paged KV cache.

  # far-memory KV store under the hybrid plane:
  PYTHONPATH=src python -m repro.launch.serve --mode kv --plane hybrid \
      --workload mcd_cl --steps 200

  # LM decode with the plane-managed cache (smoke config):
  PYTHONPATH=src python -m repro.launch.serve --mode lm --arch llama3-8b \
      --tokens 32 --batch 4
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfgs
from repro.core.layout import PlaneConfig
from repro.data import kvworkload
from repro.launch import compile_cache
from repro.models import api
from repro.serving.engine import Engine, EngineConfig


def kv_plane_config(num_objs: int, obj_dim: int,
                    local: float) -> PlaneConfig:
    """Store geometry: ``num_objs`` records of ``obj_dim`` f32 in 8-record
    pages, frames for ``local`` of the data pages, and a virtual page space
    three times the data (log headroom for the runtime path and the
    evacuator)."""
    data_pages = -(-num_objs // 8)
    return PlaneConfig(num_objs=num_objs, obj_dim=obj_dim, page_objs=8,
                       num_frames=max(int(data_pages * local), 8),
                       num_vpages=3 * data_pages, readahead=2)


def make_records(num_objs: int, obj_dim: int, seed: int) -> np.ndarray:
    """Host-side record values ``[num_objs, obj_dim]`` f32 from ``seed``."""
    rng = np.random.default_rng(seed)
    return rng.random((num_objs, obj_dim), dtype=np.float32)


def serve_kv(ecfg: EngineConfig, pcfg: PlaneConfig, data: np.ndarray,
             workload, *, interarrival: float = 0.0, mesh=None):
    """Serve ``workload`` (batches of record ids) through an ``Engine``
    holding ``data`` and check every served row against ``data[ids]``.

    Returns ``(report, engine)``: the engine's run report plus
    ``setup_s`` (state build and warm-up compiles), ``requests`` and
    ``mismatches`` (served rows that differ from the data)."""
    t0 = time.time()
    eng = Engine(ecfg, pcfg, data, mesh=mesh)
    jax.block_until_ready(eng.state)
    setup_s = time.time() - t0
    served = []
    rep = eng.run(workload, offered_interarrival_s=interarrival,
                  on_batch=lambda ids, rows: served.append((ids, rows)))
    ids = np.concatenate([np.asarray(i, np.int64) for i, _ in served])
    rows = np.concatenate([np.asarray(r) for _, r in served])
    bad = np.any(rows != data[ids], axis=1)
    rep.update(setup_s=setup_s, requests=int(ids.size),
               mismatches=int(bad.sum()))
    return rep, eng


def main_kv(args):
    pcfg = kv_plane_config(args.objects, args.obj_dim, args.local)
    data = make_records(args.objects, args.obj_dim, args.seed)
    wl = kvworkload.WORKLOADS[args.workload](args.objects, args.batch,
                                             args.steps, seed=args.seed)
    rep, _ = serve_kv(EngineConfig(plane=args.plane, batch=args.batch),
                      pcfg, data, wl, interarrival=args.interarrival)
    print(f"[serve:kv] plane={args.plane} workload={args.workload} "
          f"local={args.local:.0%}")
    print(f"  latency: {rep['latency']}")
    print(f"  stats:   {rep['stats']}")
    print(f"  paging fraction: {rep['paging_fraction']:.2f}")
    print(f"  verified: {rep['requests'] - rep['mismatches']}/"
          f"{rep['requests']} rows match the data")
    if rep["mismatches"]:
        raise SystemExit(f"{rep['mismatches']} served rows differ from data")


def serve_lm(args):
    cfg = cfgs.get_smoke(args.arch)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    shape = cfgs.ShapeConfig("serve", 1024, args.batch, "decode")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    state = api.init_decode_state(cfg, shape)
    step = jax.jit(api.decode_step(cfg, shape))
    tok = jax.random.randint(jax.random.PRNGKey(1), (args.batch,), 0,
                             cfg.vocab)
    state, logits = step(params, state, tok)   # compile
    t0 = time.time()
    toks = []
    for t in range(args.tokens):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32) % cfg.vocab
        state, logits = step(params, state, tok)
        toks.append(np.asarray(tok))
    dt = time.time() - t0
    print(f"[serve:lm] arch={args.arch} batch={args.batch} "
          f"decoded {args.tokens} tokens in {dt:.2f}s "
          f"({args.tokens * args.batch / dt:.1f} tok/s)")
    print(f"  sample continuation: {[int(t[0]) for t in toks[:16]]}")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--mode", choices=["kv", "lm"], default="kv")
    # kv mode
    p.add_argument("--plane", default="hybrid",
                   choices=["hybrid", "paging", "object"])
    p.add_argument("--workload", default="mcd_cl",
                   choices=list(kvworkload.WORKLOADS))
    p.add_argument("--objects", type=int, default=4096)
    p.add_argument("--obj-dim", type=int, default=32,
                   help="record width in f32 elements")
    p.add_argument("--local", type=float, default=0.25)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--interarrival", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    # lm mode
    p.add_argument("--arch", default="llama3-8b")
    p.add_argument("--tokens", type=int, default=32)
    args = p.parse_args()
    compile_cache.enable()
    if args.mode == "kv":
        main_kv(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
