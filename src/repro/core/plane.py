"""The Atlas hybrid data plane: batched access, evacuation, writeback.

``access`` is the batched read barrier (paper Algorithm 1/2), served by the
plan-then-execute engine in :mod:`repro.core.batch`: the whole request
batch is classified against the batch-entry state, misses are deduped and
split by PSF into a paging plan (whole-page fetches, vaddrs stable) and a
runtime plan (objects moved to the ingress fill page, smart pointers
rewritten), profiling (CAT card bits, access bits, page clocks) is applied
in one vectorized pass, and results are read with one batched gather.
``mode="reference"`` replays the same plan through a scalar executor — the
equivalence oracle.

Eviction happens only page-granularly inside ``paths.alloc_frame`` (egress
path, paper §4.1) — the PSF of the victim is recomputed from its CAR
there.  ``evacuate`` is the concurrent compactor analogue: victims are
selected by garbage ratio and their live rows are re-packed hot/cold
through ``ops.compact_pages``, one batched row gather.
"""
from __future__ import annotations

import functools
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..kernels import ops as kops
from . import batch as batch_lib
from . import paths
from . import state as st
from .layout import (CAR_THR_MAX, CAR_THR_MIN, FREE, LOCAL, REMOTE,
                     PlaneConfig)


# --------------------------------------------------------------------------
# batched access (the hybrid ingress) — plan-then-execute engine
# --------------------------------------------------------------------------

def access(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray, *,
           mode: str | None = None):
    """Batched hybrid access (the read barrier; DESIGN.md §3).

    Shape contract: ``obj_ids`` is ``[R]`` int32, negative ids are padded
    no-ops; returns ``(state, rows[R, D])`` with zero rows for padded or
    fault-unserved requests.  Determinism invariant: ``mode="batch"``
    (vectorized engine, default) and ``mode="reference"`` (scalar oracle)
    execute the identical plan and agree byte-for-byte on state and rows;
    ``None`` defers to ``cfg.access_mode``."""
    return batch_lib.access(cfg, s, obj_ids, mode=mode)


def update(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray,
           rows: jnp.ndarray, *, mode: str | None = None) -> st.PlaneState:
    """Batched write-through-local: fault in, overwrite rows, mark dirty
    (DESIGN.md §3; fault masking §6/§6c).

    Shape contract: ``obj_ids`` ``[R]`` int32 (negative = padded no-op),
    ``rows`` ``[R, D]``; returns the new state.  Determinism invariant: a
    fault-masked (unserved) request writes nothing to either tier — under
    any same-seed schedule both access modes produce bit-identical
    states."""
    return batch_lib.update(cfg, s, obj_ids, rows, mode=mode)


# --------------------------------------------------------------------------
# memoized jit entry points
# --------------------------------------------------------------------------
# ``jax.jit(partial(access, cfg))`` builds a NEW callable every time, so two
# call sites with the same config compile the same program twice.  These
# helpers key the jitted executable on the (hashable) PlaneConfig — every
# engine/test/benchmark in a process shares one compilation per config.
# The thin wrappers normalize defaulted arguments before the cache lookup
# (lru_cache keys raw call args, so ``f(cfg)`` and ``f(cfg, "batch")``
# would otherwise compile twice).

@functools.lru_cache(maxsize=None)
def _jitted_access(cfg: PlaneConfig, mode: str):
    return jax.jit(partial(access, cfg, mode=mode))


def jitted_access(cfg: PlaneConfig, mode: str | None = None):
    return _jitted_access(cfg, mode or cfg.access_mode)


@functools.lru_cache(maxsize=None)
def _jitted_update(cfg: PlaneConfig, mode: str):
    return jax.jit(partial(update, cfg, mode=mode))


def jitted_update(cfg: PlaneConfig, mode: str | None = None):
    return _jitted_update(cfg, mode or cfg.access_mode)


# plan/execute split entry points: the serving engine dispatches these as
# two device calls per batch so the host can enqueue batch N+1's plan while
# batch N's execute runs (double-buffered dispatch, see serving.engine)

@functools.lru_cache(maxsize=None)
def _jitted_plan_access(cfg: PlaneConfig, degraded: bool):
    return jax.jit(partial(batch_lib.plan_access, cfg, degraded=degraded))


def jitted_plan_access(cfg: PlaneConfig, degraded: bool = False):
    return _jitted_plan_access(cfg, degraded)


@functools.lru_cache(maxsize=None)
def _jitted_execute_access(cfg: PlaneConfig, mode: str):
    return jax.jit(partial(batch_lib.execute_access, cfg, mode=mode))


def jitted_execute_access(cfg: PlaneConfig, mode: str | None = None):
    return _jitted_execute_access(cfg, mode or cfg.access_mode)


@functools.lru_cache(maxsize=None)
def _jitted_evacuate(cfg: PlaneConfig, garbage_threshold: float | None,
                     max_pages: int, clear_access: bool):
    return jax.jit(partial(evacuate, cfg, garbage_threshold=garbage_threshold,
                           max_pages=max_pages, clear_access=clear_access))


def jitted_evacuate(cfg: PlaneConfig, garbage_threshold: float | None = None,
                    max_pages: int = 16, clear_access: bool = True):
    return _jitted_evacuate(cfg, garbage_threshold, max_pages, clear_access)


@functools.lru_cache(maxsize=None)
def _jitted_plan_evacuate(cfg: PlaneConfig, garbage_threshold: float | None,
                          max_pages: int):
    return jax.jit(partial(plan_evacuate, cfg,
                           garbage_threshold=garbage_threshold,
                           max_pages=max_pages))


def jitted_plan_evacuate(cfg: PlaneConfig,
                         garbage_threshold: float | None = None,
                         max_pages: int = 16):
    return _jitted_plan_evacuate(cfg, garbage_threshold, max_pages)


@functools.lru_cache(maxsize=None)
def _jitted_execute_evacuate(cfg: PlaneConfig,
                             garbage_threshold: float | None,
                             clear_access: bool):
    return jax.jit(partial(execute_evacuate, cfg,
                           garbage_threshold=garbage_threshold,
                           clear_access=clear_access))


def jitted_execute_evacuate(cfg: PlaneConfig,
                            garbage_threshold: float | None = None,
                            clear_access: bool = True):
    return _jitted_execute_evacuate(cfg, garbage_threshold, clear_access)


@functools.lru_cache(maxsize=None)
def _jitted_advance_epoch(cfg: PlaneConfig):
    return jax.jit(partial(advance_epoch, cfg))


def jitted_advance_epoch(cfg: PlaneConfig):
    return _jitted_advance_epoch(cfg)


# --------------------------------------------------------------------------
# epoch governor (always-on profiling, adaptive path selection)
# --------------------------------------------------------------------------

def advance_epoch(cfg: PlaneConfig, s: st.PlaneState, *,
                  traffic=None) -> st.PlaneState:
    """Close one profiling epoch: fold the card-table window into the
    per-page CAR EMA (``kernels.cat_decay``), let the governor adapt the
    PSF threshold from the epoch's observed paging-vs-runtime traffic, and
    recompute every allocated page's PSF from the decayed CAR — path
    selection adapts *online*, without waiting for a page-out.

    Governor law: with ``d_page``/``d_obj`` the bytes each ingress path
    moved since the last epoch, the threshold moves by ``governor_gain *
    (d_page - d_obj) / total`` (clipped to [CAR_THR_MIN, CAR_THR_MAX]).
    When paging traffic dominates, the bar for the paging path rises —
    sparse pages that were amplifying I/O drop to the runtime path; when
    object traffic dominates, the bar falls and co-accessed pages return
    to bulk paging.  At equilibrium the two paths carry comparable bytes,
    which is where the hybrid's amplification-vs-overhead tradeoff sits
    (paper Fig. 10's flat optimum around 0.8-0.9).

    The card table is cleared to open the next window (``page_out``
    therefore blends the instantaneous window CAR with the EMA).  Pure
    vectorized ``state -> state`` math — identical under both access
    modes, bit-deterministic (no RNG, no data-dependent shapes).  Owned
    by DESIGN.md §4a.

    ``traffic``: optional ``(d_page, d_obj)`` float32 byte totals overriding
    the locally-derived deltas — the sharded plane passes the GLOBAL
    aggregate here so every shard's governor sees the same imbalance (and
    their thresholds move in lockstep), while all other epoch state stays
    per-shard."""
    allocated = s.backing != FREE
    ema = kops.cat_decay(s.cat, s.car_ema, s.alloc_count,
                         decay=cfg.car_decay, impl=cfg.kernel_impl)
    ema = jnp.where(allocated, ema, 0.0)

    if traffic is None:
        d_page = ((s.stats.page_ins - s.epoch_page_ins).astype(jnp.float32)
                  * cfg.page_bytes)
        d_obj = ((s.stats.obj_ins - s.epoch_obj_ins).astype(jnp.float32)
                 * cfg.row_bytes)
    else:
        d_page, d_obj = traffic
    total = d_page + d_obj
    imbalance = jnp.where(total > 0.0,
                          (d_page - d_obj) / jnp.maximum(total, 1.0), 0.0)
    thr = jnp.clip(s.car_thr + jnp.float32(cfg.governor_gain) * imbalance,
                   CAR_THR_MIN, CAR_THR_MAX)

    new_psf = jnp.where(allocated, ema >= thr, s.psf)
    flip_p = jnp.sum((allocated & ~s.psf & new_psf).astype(jnp.int32))
    flip_r = jnp.sum((allocated & s.psf & ~new_psf).astype(jnp.int32))
    return s._replace(
        cat=jnp.zeros_like(s.cat),        # open the next epoch window
        car_ema=ema, car_thr=thr, psf=new_psf,
        epoch=s.epoch + 1,
        epoch_page_ins=s.stats.page_ins, epoch_obj_ins=s.stats.obj_ins,
        stats=st.bump(s.stats, epochs=1, psf_to_paging=flip_p,
                      psf_to_runtime=flip_r))


# --------------------------------------------------------------------------
# evacuation (concurrent compactor analogue, paper §4.3)
# --------------------------------------------------------------------------

class EvacPlan(NamedTuple):
    """Victim selection for one evacuation slice (fixed ``[k]`` shapes, so
    the serving engine can dispatch planning and execution as separate
    async device calls into pipeline bubbles)."""

    victims: jnp.ndarray   # [k] int32 candidate vpages (garbage-ratio top-k)
    ok: jnp.ndarray        # [k] bool  candidate was eligible at plan time


def plan_evacuate(cfg: PlaneConfig, s: st.PlaneState,
                  garbage_threshold: float | None = None,
                  max_pages: int = 16) -> EvacPlan:
    """Select at most ``max_pages`` evacuation victims: the local, unpinned
    pages with the highest dead-slot ratio above the threshold."""
    thr = (cfg.evac_garbage_threshold if garbage_threshold is None
           else garbage_threshold)
    allocated_all = s.alloc_count
    dead_all = allocated_all - s.live_count
    ratio_all = dead_all.astype(jnp.float32) / jnp.maximum(allocated_all, 1)
    eligible = ((s.backing == LOCAL) & (s.pin == 0) & (allocated_all > 0)
                & (ratio_all > thr))
    score = jnp.where(eligible, ratio_all, -1.0)
    k = min(max_pages, cfg.num_vpages)
    _, victims = lax.top_k(score, k)
    return EvacPlan(victims=victims, ok=score[victims] > -1.0)


def execute_evacuate(cfg: PlaneConfig, s: st.PlaneState, plan: EvacPlan,
                     garbage_threshold: float | None = None, *,
                     clear_access: bool = True, shard=None) -> st.PlaneState:
    """Compact the planned victim pages (hot/cold segregation by access
    bit, ``ops.compact_pages`` page assembly).  Each victim's eligibility is
    re-checked against the *current* state — a stale plan entry (page
    evicted, drained, or pinned since planning) is skipped, so a plan may
    safely execute several dispatch gaps after it was made.

    Egress faults (DESIGN.md §6c) gate each victim the same way: when
    ``cfg.faults.egress_fail(s.step, vpage, shard)`` holds, the victim is
    skipped whole this slice — no rows move, no page is freed, and
    ``stats.egress_failures`` counts the blocked compaction.  The source
    page stays live and eligible, so a later slice retries it.

    ``clear_access=False`` keeps the access bits (paper: the evacuator
    clears them "at the end of each evacuation" — for background slices
    that is the end of a full round, not of every slice; the serving
    engine clears on its round boundary)."""
    thr = (cfg.evac_garbage_threshold if garbage_threshold is None
           else garbage_threshold)
    P, V, F, O = cfg.page_objs, cfg.num_vpages, cfg.num_frames, cfg.num_objs
    D = cfg.obj_dim
    victims, victim_ok = plan.victims, plan.ok
    k = victims.shape[0]
    fc = cfg.faults
    shard_i = 0 if shard is None else shard

    def page_body(i, s):
        v = victims[i]
        # re-check eligibility against the *current* state (earlier victims
        # may have evicted or drained this page while allocating
        # destination frames)
        allocated = s.alloc_count[v]
        dead = allocated - s.live_count[v]
        garbage_ratio = dead.astype(jnp.float32) / jnp.maximum(allocated, 1)
        selected = (
            victim_ok[i]
            & (s.backing[v] == LOCAL)
            & (s.pin[v] == 0)
            & (allocated > 0)
            & (garbage_ratio > thr)
        )
        if fc is not None and fc.egress_active:
            # an evacuation moves rows into (possibly fresh) remote-backed
            # log pages — a blocked write skips the victim atomically
            efail = fc.egress_fail(s.step, v, shard_i)
            s = s._replace(stats=st.bump(
                s.stats,
                egress_failures=(selected & efail).astype(jnp.int32)))
            selected = selected & ~efail

        def evacuate_page(s):
            # pin the source so destination allocation can't page it out
            # from under the compactor (Invariant #3 mechanism)
            s = paths.pin_page(s, v)
            f_src = jnp.maximum(s.frame_of[v], 0)
            objs = s.obj_of[v]                      # [P]
            occ = objs >= 0
            hotm = occ & s.access[v]
            coldm = occ & ~s.access[v]
            was_carded = s.cat[v]
            n_moved = jnp.sum(occ.astype(jnp.int32))

            # plan both append streams (allocates/pins fresh pages first;
            # retired cursors stay pinned until the compact writes land)
            s, hv, hslot, hcur, hc, hf, hret = batch_lib.plan_append_stream(
                cfg, s, "evac_hot_vpage", hotm)
            s, cv, cslot, ccur, cc, cf, cret = batch_lib.plan_append_stream(
                cfg, s, "evac_cold_vpage", coldm)
            v_dst = jnp.where(hotm, hv, cv)
            s_dst = jnp.where(hotm, hslot, cslot)

            # assemble the (up to four) destination pages in one row
            # gather: each destination slot DMAs its source row directly
            src_flat = f_src * P + jnp.arange(P, dtype=jnp.int32)
            dest_pages = jnp.stack([hc, hf, cc, cf])          # [4]
            dpi = jnp.where(hotm, jnp.where(hcur, 0, 1),
                            jnp.where(coldm, jnp.where(ccur, 2, 3), 4))
            plan = jnp.full((4, P), -1, jnp.int32)
            plan = plan.at[dpi, jnp.where(occ, s_dst, 0)].set(src_flat)
            assembled = kops.compact_pages(
                s.frames.reshape(F * P, D), plan.reshape(4 * P),
                page_objs=P, impl=cfg.kernel_impl)            # [4, P, D]
            dest_f = jnp.maximum(s.frame_of[jnp.maximum(dest_pages, 0)], 0)
            existing = s.frames[dest_f]
            merged = jnp.where((plan >= 0)[..., None], assembled, existing)
            frames = s.frames.at[jnp.where(dest_pages >= 0, dest_f, F)].set(
                merged)

            # smart pointers + occupancy + preserved profiling bits
            # (the evacuator preserves card bits across the move, §4.3)
            # 2-D scatters (row V = dropped): a flat [V*P] bool scatter
            # takes the TPU compiler minutes at 1 Mi records
            dst_v = jnp.where(occ, v_dst, V)
            s = s._replace(
                frames=frames,
                obj_loc=s.obj_loc.at[jnp.where(occ, objs, O)].set(
                    v_dst * P + s_dst),
                obj_of=s.obj_of.at[dst_v, s_dst].set(objs),
                cat=s.cat.at[dst_v, s_dst].set(was_carded),
                access=s.access.at[dst_v, s_dst].set(hotm),
                stats=st.bump(s.stats, evac_moved=n_moved),
            )
            # the moved rows are in place — NOW the retired cursors may be
            # unpinned (they are ordinary unpinned pages from here on)
            pin = s.pin.at[jnp.where(hret >= 0, hret, V)].add(-1)
            pin = pin.at[jnp.where(cret >= 0, cret, V)].add(-1)
            s = s._replace(pin=pin)
            # kill the source copies wholesale
            s = s._replace(obj_of=s.obj_of.at[v].set(-1),
                           live_count=s.live_count.at[v].set(0))
            s = paths.unpin_page(s, v)
            # the pin kept GC away; reclaim the drained source explicitly
            still_here = s.backing[v] == LOCAL
            s = lax.cond(jnp.logical_and(still_here, s.live_count[v] == 0),
                         lambda s: paths.free_page(cfg, s, v), lambda s: s, s)
            return s._replace(stats=st.bump(s.stats, evac_pages=1))

        return lax.cond(selected, evacuate_page, lambda s: s, s)

    s = lax.fori_loop(0, k, page_body, s)
    if clear_access:
        s = s._replace(access=jnp.zeros_like(s.access))
    return s


def evacuate(cfg: PlaneConfig, s: st.PlaneState,
             garbage_threshold: float | None = None,
             max_pages: int = 16, *,
             clear_access: bool = True, shard=None) -> st.PlaneState:
    """Foreground evacuation: plan + execute in one call.

    Live objects are segregated by their access bit: recently-accessed
    ("hot") objects are appended to a dedicated hot destination page,
    the rest to a cold one — manufacturing the spatial locality that lets
    subsequent accesses take the cheap paging path.  Each victim's moves
    are planned as two append streams and executed with the
    ``ops.compact_pages`` row gather (one gather-DMA per
    destination row) instead of a per-slot append chain.  All access bits
    are cleared at the end (paper: "cleared by the evacuator at the end of
    each evacuation").

    Evacuation is *incremental*: at most ``max_pages`` victims (the highest
    garbage ratios) are compacted per call, bounding the pause the
    concurrent evacuator imposes on the application.  The serving engine
    goes further and schedules ``plan_evacuate``/``execute_evacuate`` as
    small background slices inside pipeline bubbles (``evac_budget``) —
    this wrapper is the blocking-foreground composition of the same two
    halves.

    Shape contract: pure ``state -> state`` (fixed ``[max_pages]`` victim
    plan).  Determinism invariant: victim selection and the egress-fault
    gate (§6c) are functions of state and ``cfg.faults`` only — same-seed
    runs compact identical pages.  Owned by DESIGN.md §4c (slice
    scheduling) and §6c (egress faults); ``shard`` keys the per-shard
    fault stream for the sharded plane."""
    plan = plan_evacuate(cfg, s, garbage_threshold, max_pages)
    return execute_evacuate(cfg, s, plan, garbage_threshold,
                            clear_access=clear_access, shard=shard)


# --------------------------------------------------------------------------
# maintenance / introspection
# --------------------------------------------------------------------------

def writeback_all(cfg: PlaneConfig, s: st.PlaneState) -> st.PlaneState:
    """Flush every dirty local page to the slab (keeps pages resident)."""

    def body(f, s):
        v = s.vpage_of[f]
        flush = jnp.logical_and(v >= 0, s.dirty[jnp.maximum(v, 0)])

        def do(s):
            slab = lax.dynamic_update_index_in_dim(s.slab, s.frames[f], v, axis=0)
            return s._replace(slab=slab, dirty=s.dirty.at[v].set(False))

        return lax.cond(flush, do, lambda s: s, s)

    return lax.fori_loop(0, cfg.num_frames, body, s)


def evict_all(cfg: PlaneConfig, s: st.PlaneState) -> st.PlaneState:
    """Page out every unpinned local page (shutdown / memory-pressure)."""

    def body(f, s):
        v = s.vpage_of[f]
        can = jnp.logical_and(v >= 0, s.pin[jnp.maximum(v, 0)] == 0)
        return lax.cond(can, lambda s: paths.page_out(cfg, s, f), lambda s: s, s)

    return lax.fori_loop(0, cfg.num_frames, body, s)


def peek(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray) -> jnp.ndarray:
    """Read object rows wherever they live, with NO state change (oracle)."""
    vaddr = s.obj_loc[obj_ids]
    v, slot = vaddr // cfg.page_objs, vaddr % cfg.page_objs
    local = s.backing[v] == LOCAL
    f = jnp.maximum(s.frame_of[v], 0)
    return jnp.where(local[:, None], s.frames[f, slot], s.slab[v, slot])


def occupancy(cfg: PlaneConfig, s: st.PlaneState) -> jnp.ndarray:
    """Fraction of local frames in use."""
    return jnp.mean((s.vpage_of >= 0).astype(jnp.float32))


def paging_fraction(cfg: PlaneConfig, s: st.PlaneState) -> jnp.ndarray:
    """Fraction of allocated pages whose PSF is paging (paper Fig. 7)."""
    allocated = s.backing != FREE
    pg = jnp.sum((s.psf & allocated).astype(jnp.int32))
    return pg / jnp.maximum(jnp.sum(allocated.astype(jnp.int32)), 1)


def check_invariants(cfg: PlaneConfig, s: st.PlaneState) -> dict:
    """Structural invariants (host-side; used by property tests)."""
    sn = jax.device_get(s)
    P, V, F = cfg.page_objs, cfg.num_vpages, cfg.num_frames
    out = {}

    # smart pointers and slot occupancy agree
    ok = True
    for o in range(cfg.num_objs):
        va = int(sn.obj_loc[o])
        if va < 0:
            continue
        ok &= sn.obj_of[va // P, va % P] == o
    out["obj_loc_obj_of_consistent"] = bool(ok)

    live = (sn.obj_of >= 0).sum(axis=1)
    out["live_count_correct"] = bool(np.all(live == sn.live_count))
    out["alloc_ge_live"] = bool(np.all(sn.alloc_count >= sn.live_count))

    # frame table is a bijection on LOCAL pages
    ok = True
    for v in range(V):
        if sn.backing[v] == LOCAL:
            f = int(sn.frame_of[v])
            ok &= 0 <= f < F and sn.vpage_of[f] == v
        else:
            ok &= sn.frame_of[v] == -1
    for f in range(F):
        v = int(sn.vpage_of[f])
        if v >= 0:
            ok &= sn.backing[v] == LOCAL and sn.frame_of[v] == f
    out["frame_bijection"] = bool(ok)

    out["pins_nonnegative"] = bool(np.all(sn.pin >= 0))
    # outside an access batch the only standing pins are the fill cursors
    cursors = [int(sn.fill_vpage), int(sn.evac_hot_vpage),
               int(sn.evac_cold_vpage), int(sn.remote_fill_vpage)]
    expected = np.zeros(V, np.int64)
    for c in cursors:
        if c >= 0:
            expected[c] += 1
    out["pins_are_cursor_pins"] = bool(np.all(sn.pin == expected))
    out["free_pages_empty"] = bool(np.all(sn.live_count[sn.backing == FREE] == 0))
    return out
