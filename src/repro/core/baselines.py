"""Baseline data planes, per the paper's evaluation (§5.1 "Baselines").

* ``paging_access``  — Fastswap analogue: page-granular ingress **and**
  egress, kernel-style sequential readahead, no object machinery at all.
  Resource-cheap (victim selection is O(frames)) but suffers I/O
  amplification on sparse access.

* ``object_access``  — AIFM analogue: object-granular ingress **and**
  egress.  Maintains a true object-level LRU (per-object timestamps) and on
  memory pressure scans it to evict the coldest objects individually,
  scattering them into a remote log.  ``lru_scan_budget`` models the
  CPU-starved regime from the paper (scan a bounded window -> evict
  near-arbitrary objects -> thrashing).

Both ingress paths run on the plan-then-execute batch engine
(:mod:`repro.core.batch`) so all three planes share the same data movers
and the benchmarks compare pure policy differences; the object plane's
LRU egress loop below stays scalar because the paper's point is exactly
that object-granular egress serializes on metadata scans.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import batch as batch_lib
from . import paths
from . import state as st
from .layout import FREE, LOCAL, REMOTE, PlaneConfig

INF32 = jnp.iinfo(jnp.int32).max


# --------------------------------------------------------------------------
# Fastswap analogue
# --------------------------------------------------------------------------

def paging_access(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray,
                  *, mode: str | None = None, shard=None,
                  degraded: bool = False):
    """Page-granular plane: every miss pages in (with readahead); no CAT,
    no PSF consultation, no object moves.  Egress is the shared page-out."""
    return batch_lib.paging_access(cfg, s, obj_ids, mode=mode, shard=shard,
                                   degraded=degraded)


# --------------------------------------------------------------------------
# AIFM analogue
# --------------------------------------------------------------------------

def _object_out_coldest(cfg: PlaneConfig, s: st.PlaneState) -> st.PlaneState:
    """Evict one object chosen by the object-level LRU.

    Full scan: argmin of per-object last-access among local objects — the
    O(num_objs) cost the paper charges object planes for.  With
    ``lru_scan_budget > 0`` only a rotating window is scanned (CPU-starved
    regime -> near-arbitrary victims)."""
    O = cfg.num_objs
    vp = s.obj_loc // cfg.page_objs
    local = (s.obj_loc >= 0) & (s.backing[jnp.clip(vp, 0, cfg.num_vpages - 1)] == LOCAL)
    unpinned = s.pin[jnp.clip(vp, 0, cfg.num_vpages - 1)] == 0

    if cfg.lru_scan_budget and cfg.lru_scan_budget < O:
        B = cfg.lru_scan_budget
        idx = (s.lru_hand + jnp.arange(B)) % O
        cand_mask = local[idx] & unpinned[idx]
        score = jnp.where(cand_mask, s.obj_last[idx], INF32)
        o = idx[jnp.argmin(score)]
        scanned = B
        s = s._replace(lru_hand=(s.lru_hand + B) % O)
        valid = jnp.any(cand_mask)
    else:
        score = jnp.where(local & unpinned, s.obj_last, INF32)
        o = jnp.argmin(score).astype(jnp.int32)
        scanned = O
        valid = jnp.any(local & unpinned)

    def evict(s):
        va = s.obj_loc[o]
        v, slot = va // cfg.page_objs, va % cfg.page_objs
        row = s.frames[s.frame_of[v], slot]
        s = _append_obj_remote(cfg, s, o, row)
        return s._replace(stats=st.bump(s.stats, obj_outs=1))

    s = s._replace(stats=st.bump(s.stats, lru_scans=scanned))
    return lax.cond(valid, evict, lambda s: s, s)


def _append_obj_remote(cfg: PlaneConfig, s: st.PlaneState, o, row) -> st.PlaneState:
    """Move object ``o`` to the remote log (object-granular egress).

    Objects evicted at different times land on unrelated remote pages —
    the locality-disruption effect the paper attributes to object egress."""

    def need_new(s):
        cur = s.remote_fill_vpage
        return jnp.logical_or(
            cur < 0, s.alloc_count[jnp.maximum(cur, 0)] >= cfg.page_objs)

    def alloc_remote_log(s):
        cur = s.remote_fill_vpage
        s = lax.cond(cur >= 0, lambda s: paths.unpin_page(s, cur), lambda s: s, s)
        v = jnp.argmax(s.backing == FREE).astype(jnp.int32)
        s = s._replace(
            backing=s.backing.at[v].set(REMOTE),
            alloc_count=s.alloc_count.at[v].set(0),
            live_count=s.live_count.at[v].set(0),
            obj_of=s.obj_of.at[v].set(-1),
            car_ema=s.car_ema.at[v].set(0.0),   # fresh page identity
            remote_fill_vpage=v,
        )
        return paths.pin_page(s, v)

    s = lax.cond(need_new(s), alloc_remote_log, lambda s: s, s)
    v_new = s.remote_fill_vpage
    slot_new = s.alloc_count[v_new]

    old = s.obj_loc[o]
    v_old, slot_old = old // cfg.page_objs, old % cfg.page_objs

    s = s._replace(
        slab=s.slab.at[v_new, slot_new].set(row),
        obj_loc=s.obj_loc.at[o].set(v_new * cfg.page_objs + slot_new),
        obj_of=s.obj_of.at[v_new, slot_new].set(o),
        alloc_count=s.alloc_count.at[v_new].add(1),
        live_count=s.live_count.at[v_new].add(1),
    )
    return paths._kill_old_copy(cfg, s, v_old, slot_old)


def object_reclaim(cfg: PlaneConfig, s: st.PlaneState, target_free: int
                   ) -> st.PlaneState:
    """Evict coldest objects until ``target_free`` frames are free (the
    object plane's egress loop; bounded by the live-object count)."""

    def free_frames(s):
        return jnp.sum((s.vpage_of < 0).astype(jnp.int32))

    def cond(s):
        return free_frames(s) < target_free

    def body(s):
        s0_outs = s.stats.obj_outs

        def one(k, s):
            return _object_out_coldest(cfg, s)

        s = lax.fori_loop(0, cfg.object_evict_batch, one, s)
        # no progress (everything pinned) -> bail by faking success
        stuck = s.stats.obj_outs == s0_outs
        return lax.cond(stuck, lambda s: s, lambda s: s, s)

    # hard bound: each iteration evicts object_evict_batch objects
    max_iter = (cfg.num_objs // max(cfg.object_evict_batch, 1)) + 2

    def bounded_cond(carry):
        s, it = carry
        return jnp.logical_and(cond(s), it < max_iter)

    def bounded_body(carry):
        s, it = carry
        return body(s), it + 1

    s, _ = lax.while_loop(bounded_cond, bounded_body,
                          (s, jnp.asarray(0, jnp.int32)))
    return s


def object_access(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray,
                  reclaim_free_target: int = 2, *, mode: str | None = None,
                  shard=None, degraded: bool = False):
    """Object-granular plane (AIFM analogue): every miss object-fetches;
    after the batch, reclaim via the object-level LRU if frames are tight."""
    return batch_lib.object_access(cfg, s, obj_ids, reclaim_free_target,
                                   mode=mode, reclaim=object_reclaim,
                                   shard=shard, degraded=degraded)


# memoized jit entry points (one compilation per config per process — see
# plane.jitted_access; wrappers normalize ``mode`` before the cache lookup;
# ``donate=True`` as in plane.jitted_execute_access)

@functools.lru_cache(maxsize=None)
def _jitted_paging_access(cfg: PlaneConfig, mode: str):
    return jax.jit(st.named_partial(paging_access, cfg, mode=mode))


def jitted_paging_access(cfg: PlaneConfig, mode: str | None = None):
    return _jitted_paging_access(cfg, mode or cfg.access_mode)


@functools.lru_cache(maxsize=None)
def _jitted_object_access(cfg: PlaneConfig, mode: str):
    return jax.jit(st.named_partial(object_access, cfg, mode=mode))


def jitted_object_access(cfg: PlaneConfig, mode: str | None = None):
    return _jitted_object_access(cfg, mode or cfg.access_mode)


# plan/execute split entry points (pipelined serving dispatch — the plan of
# batch N+1 is enqueued while batch N's execute runs; see serving.engine)

@functools.lru_cache(maxsize=None)
def _jitted_plan_paging(cfg: PlaneConfig, degraded: bool):
    return jax.jit(st.named_partial(batch_lib.plan_access, cfg,
                                    split_by_psf=False, degraded=degraded))


def jitted_plan_paging(cfg: PlaneConfig, degraded: bool = False):
    return _jitted_plan_paging(cfg, degraded)


@functools.lru_cache(maxsize=None)
def _jitted_execute_paging(cfg: PlaneConfig, mode: str, donate: bool = False):
    return st.jit_state(st.named_partial(batch_lib.execute_paging_access,
                                         cfg, mode=mode), donate)


def jitted_execute_paging(cfg: PlaneConfig, mode: str | None = None, *,
                          donate: bool = False):
    return _jitted_execute_paging(cfg, mode or cfg.access_mode, donate)


@functools.lru_cache(maxsize=None)
def _jitted_plan_object(cfg: PlaneConfig, degraded: bool):
    return jax.jit(st.named_partial(batch_lib.plan_access, cfg,
                                    all_runtime=True, degraded=degraded))


def jitted_plan_object(cfg: PlaneConfig, degraded: bool = False):
    return _jitted_plan_object(cfg, degraded)


@functools.lru_cache(maxsize=None)
def _jitted_execute_object(cfg: PlaneConfig, mode: str, donate: bool = False):
    return st.jit_state(st.named_partial(batch_lib.execute_object_access,
                                         cfg, mode=mode,
                                         reclaim=object_reclaim), donate)


def jitted_execute_object(cfg: PlaneConfig, mode: str | None = None, *,
                          donate: bool = False):
    return _jitted_execute_object(cfg, mode or cfg.access_mode, donate)
