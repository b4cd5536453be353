"""Plane state pytree and constructors."""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import layout
from .layout import FREE, LOCAL, REMOTE, PlaneConfig


class PlaneStats(NamedTuple):
    """Event counters (int32 counts; byte totals derived host-side via
    ``PlaneConfig.row_bytes``/``page_bytes`` so no 64-bit arithmetic is needed
    on device)."""

    hits: jnp.ndarray            # resident accesses
    misses: jnp.ndarray          # faulting accesses
    page_ins: jnp.ndarray        # paging-path ingress events (pages)
    obj_ins: jnp.ndarray         # runtime-path ingress events (objects)
    page_outs: jnp.ndarray       # egress events (pages)
    dirty_page_outs: jnp.ndarray # egress events that wrote data back
    psf_to_paging: jnp.ndarray   # PSF flips runtime->paging (page-out / epoch)
    psf_to_runtime: jnp.ndarray  # PSF flips paging->runtime (page-out / epoch)
    evac_moved: jnp.ndarray      # objects moved by the evacuator
    evac_pages: jnp.ndarray      # pages reclaimed by the evacuator
    obj_outs: jnp.ndarray        # object-granular egress (object-plane baseline)
    lru_scans: jnp.ndarray       # objects scanned by object-level LRU (baseline)
    prefetch_issued: jnp.ndarray # prefetch page-ins (subset of page_ins)
    prefetch_used: jnp.ndarray   # prefetched pages later hit by a demand access
    epochs: jnp.ndarray          # advance_epoch invocations (governor runs)
    ingress_spills: jnp.ndarray  # sharded-exchange requests deferred a round
    #                              (per_shard_budget overflow, shardplane)
    fetch_failures: jnp.ndarray  # planned fetches masked off by the fault
    #                              model (repro.core.faults) — each left its
    #                              request unserved this tick
    egress_failures: jnp.ndarray # remote writes (eviction writeback, remote
    #                              update, evacuation victim, KV append)
    #                              blocked by the fault model — the write was
    #                              skipped atomically, neither tier mutated

    @classmethod
    def zeros(cls) -> "PlaneStats":
        z = jnp.zeros((), jnp.int32)
        return cls(*([z] * len(cls._fields)))


class PlaneState(NamedTuple):
    """Functional state of the hybrid data plane.

    All shapes are static; every plane operation is a pure
    ``(state, request) -> (state, result)`` function (jit/shard_map safe).
    """

    # --- storage tiers -------------------------------------------------
    frames: jnp.ndarray      # [F, P, D]  local tier ("HBM")
    slab: jnp.ndarray        # [V, P, D]  far tier  (slot id == vpage id)
    # --- page tables ----------------------------------------------------
    backing: jnp.ndarray     # [V] int8   FREE / LOCAL / REMOTE
    frame_of: jnp.ndarray    # [V] int32  frame id when LOCAL else -1
    vpage_of: jnp.ndarray    # [F] int32  inverse map, -1 = free frame
    # --- smart pointers ---------------------------------------------------
    obj_loc: jnp.ndarray     # [O] int32  vaddr (vpage*P + slot), -1 = unallocated
    obj_of: jnp.ndarray      # [V, P] int32  occupant object id, -1 = dead/empty
    live_count: jnp.ndarray  # [V] int32  live slots
    alloc_count: jnp.ndarray # [V] int32  slots ever allocated (log cursor)
    # --- always-on profiling (paper §4.1/4.3) ----------------------------
    cat: jnp.ndarray         # [V, P] bool  card access table (epoch window)
    psf: jnp.ndarray         # [V] bool     path selector flag (True = paging)
    access: jnp.ndarray      # [V, P] bool  access bit since last evacuation
    # --- epoch governor (adaptive path selection, Atlas's control loop) ---
    car_ema: jnp.ndarray     # [V] f32  decayed CAR (advance_epoch)
    car_thr: jnp.ndarray     # [] f32   adaptive PSF threshold (governor)
    epoch: jnp.ndarray       # [] int32 epoch counter
    epoch_page_ins: jnp.ndarray  # [] int32 stats.page_ins at last epoch
    epoch_obj_ins: jnp.ndarray   # [] int32 stats.obj_ins at last epoch
    prefetched: jnp.ndarray  # [V] bool  prefetched, not yet demand-touched
    # --- residency metadata ----------------------------------------------
    pin: jnp.ndarray         # [V] int32  deref counts (Invariants #2/#3)
    dirty: jnp.ndarray       # [V] bool   modified since last writeback
    clock: jnp.ndarray       # [V] int32  last-touch step (page-level recency)
    # --- log-structured allocator cursors ---------------------------------
    fill_vpage: jnp.ndarray      # [] int32  ingress fill page (-1 = none)
    evac_hot_vpage: jnp.ndarray  # [] int32  evacuation hot destination (-1)
    evac_cold_vpage: jnp.ndarray # [] int32  evacuation cold destination (-1)
    remote_fill_vpage: jnp.ndarray  # [] int32  remote log page (object-plane egress)
    step: jnp.ndarray            # [] int32  logical time
    # --- object-plane baseline metadata ------------------------------------
    obj_last: jnp.ndarray    # [O] int32  per-object last access (AIFM LRU analogue)
    lru_hand: jnp.ndarray    # [] int32   rotating scan hand for budgeted LRU
    stats: PlaneStats


def create(cfg: PlaneConfig, initial: jnp.ndarray) -> PlaneState:
    """Build a plane holding ``initial`` ([num_objs, obj_dim]) entirely in the
    far tier, densely packed into the first ``data_pages`` vpages."""
    O, D = cfg.num_objs, cfg.obj_dim
    V, P, F = cfg.num_vpages, cfg.page_objs, cfg.num_frames
    assert initial.shape == (O, D), (initial.shape, (O, D))

    dp = cfg.data_pages
    slab = jnp.zeros((V, P, D), cfg.dtype)
    pad = dp * P - O
    packed = jnp.concatenate([initial.astype(cfg.dtype),
                              jnp.zeros((pad, D), cfg.dtype)], axis=0)
    slab = slab.at[:dp].set(packed.reshape(dp, P, D))

    obj_of = jnp.full((V, P), -1, jnp.int32)
    ids = jnp.concatenate([jnp.arange(O, dtype=jnp.int32),
                           jnp.full((pad,), -1, jnp.int32)])
    obj_of = obj_of.at[:dp].set(ids.reshape(dp, P))

    # live/alloc counts for the packed prefix (last page may be partial)
    counts = np.full((V,), 0, np.int32)
    counts[:dp] = P
    if pad:
        counts[dp - 1] = P - pad
    counts = jnp.asarray(counts)

    backing = jnp.where(jnp.arange(V) < dp, REMOTE, FREE).astype(jnp.int8)

    return PlaneState(
        frames=jnp.zeros((F, P, D), cfg.dtype),
        slab=slab,
        backing=backing,
        frame_of=jnp.full((V,), -1, jnp.int32),
        vpage_of=jnp.full((F,), -1, jnp.int32),
        obj_loc=jnp.arange(O, dtype=jnp.int32),
        obj_of=obj_of,
        live_count=counts,
        alloc_count=counts,
        cat=jnp.zeros((V, P), bool),
        psf=jnp.full((V,), cfg.psf_init_paging, bool),
        access=jnp.zeros((V, P), bool),
        car_ema=jnp.zeros((V,), jnp.float32),
        car_thr=jnp.asarray(cfg.car_threshold, jnp.float32),
        epoch=jnp.asarray(0, jnp.int32),
        epoch_page_ins=jnp.asarray(0, jnp.int32),
        epoch_obj_ins=jnp.asarray(0, jnp.int32),
        prefetched=jnp.zeros((V,), bool),
        pin=jnp.zeros((V,), jnp.int32),
        dirty=jnp.zeros((V,), bool),
        clock=jnp.zeros((V,), jnp.int32),
        fill_vpage=jnp.asarray(-1, jnp.int32),
        evac_hot_vpage=jnp.asarray(-1, jnp.int32),
        evac_cold_vpage=jnp.asarray(-1, jnp.int32),
        remote_fill_vpage=jnp.asarray(-1, jnp.int32),
        step=jnp.asarray(0, jnp.int32),
        obj_last=jnp.zeros((O,), jnp.int32),
        lru_hand=jnp.asarray(0, jnp.int32),
        stats=PlaneStats.zeros(),
    )


@functools.lru_cache(maxsize=None)
def jitted_create(cfg: PlaneConfig):
    """``create`` as one compiled program: the slab is built in place, so
    set-up holds one slab copy instead of the eager ops' several."""
    return jax.jit(functools.partial(create, cfg))


def bump(stats: PlaneStats, **deltas) -> PlaneStats:
    """Increment named counters."""
    return stats._replace(**{k: getattr(stats, k) + v for k, v in deltas.items()})


# --------------------------------------------------------------------------
# shard-aware layout (the sharded far tier, repro.core.shardplane)
# --------------------------------------------------------------------------

def create_sharded(cfg: PlaneConfig, shards: int,
                   initial: jnp.ndarray) -> PlaneState:
    """Stacked ``[shards, ...]`` plane state: shard ``s`` owns global objects
    ``[s*O, (s+1)*O)`` (``O = cfg.num_objs`` is the PER-SHARD capacity), its
    own contiguous slab partition, frame pool, CAT/CAR/EMA profiling state
    and governor threshold.  ``cfg`` is the per-shard config; ``initial`` is
    the GLOBAL ``[shards*O, D]`` object array, split contiguously."""
    O, D = cfg.num_objs, cfg.obj_dim
    assert initial.shape == (shards * O, D), (initial.shape, (shards * O, D))
    return jax.vmap(lambda part: create(cfg, part))(
        initial.reshape(shards, O, D))


def shard_slice(state: PlaneState, i: int) -> PlaneState:
    """One shard's plane from a stacked ``[shards, ...]`` state (host-side
    introspection / per-shard invariant checks)."""
    return jax.tree.map(lambda x: x[i], state)
