"""Plan-then-execute batch ingress engine (the production access path).

The scalar loop in the original ``plane.access`` threaded the whole plane
state through one ``lax.fori_loop`` iteration *per request*, serializing
every dereference and never touching the batched Pallas kernels.  This
module replaces it with a three-stage engine:

  1. **Plan** (vectorized over the batch): gather ``obj_loc``, classify each
     request hit/miss against the batch-entry state, split misses by the
     page's PSF, and dedup — paging misses per *page*, runtime misses per
     *object* — in first-appearance order (sort/unique-style masking).
     The paging plan then grows a **prefetch-candidate section** (Leap-style
     majority-vote stride detection over the deduped miss stream, or the
     seed sequential window — ``cfg.prefetch``), deduped, PSF-masked and
     capped by the static ``cfg.prefetch_budget``, and every planned fetch
     (demand + prefetch) is paired with an eviction **victim frame** chosen
     in one masked top-k over the frame pool (free frames first, then
     coldest unpinned; frames holding this batch's target pages only under
     extreme pressure; prefetches never evict a target).
  2. **Execute** (all vectorized):
       * *paging plan*  — every page-out as masked scatters (write-back,
         PSF-from-CAR, CAT clear) and every page-in — demand and prefetch
         alike — in ONE batched ``kernels.gather_pages`` call; no
         per-victim ``fori_loop``/``cond`` chain,
       * *runtime plan* — fill-page capacity is computed with prefix
         arithmetic, fresh log pages are allocated up front, and the rows
         themselves move in ONE batched ``kernels.gather_rows`` +
         scatter — no per-object append chains.
  3. **Finish** (vectorized): CAT/access-bit/clock/obj_last profiling is
     applied in a single ``cat_update``-style scatter pass, and results are
     read with one batched gather over the final locations.

Batch semantics (shared by both executors, see DESIGN.md §Batch ingress):
classification happens once against batch-entry state; duplicate requests
for an already-scheduled page/object count as hits; a page evicted
mid-batch under extreme memory pressure is *not* re-faulted — the final
gather falls back to its (written-back) slab copy, so results are always
ground truth.  A **negative object id is a padded no-op request**: it
classifies as neither hit nor miss, moves and profiles nothing (all its
scatter indices are out-of-bounds sentinels, which JAX drops), and its
result row is zero — the fixed-shape padding mechanism used by the
sharded exchange (repro.core.shardplane) and partially-filled batches.

``mode="reference"`` runs the same plan through a scalar executor (one
state update per moved row / touched card, using the ``paths`` helpers) —
the oracle the equivalence tests compare the batched executor against,
byte-for-byte.

The kernel dispatch (``PlaneConfig.kernel_impl``) follows ``kernels.ops``:
``"auto"`` uses Pallas on TPU and the jnp reference elsewhere;
``"interpret"`` runs the Pallas kernel bodies in interpret mode so CPU CI
exercises the real kernel code.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from ..kernels import ops as kops
from . import paths
from . import state as st
from .layout import FREE, LOCAL, REMOTE, PlaneConfig

INF32 = jnp.iinfo(jnp.int32).max


# --------------------------------------------------------------------------
# planning primitives (vectorized dedup / classification)
# --------------------------------------------------------------------------

def _first_of(keys: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """First-appearance flags: ``out[i]`` is True iff ``mask[i]`` and no
    ``j < i`` has ``mask[j] and keys[j] == keys[i]``.  O(R^2) compare —
    trivial for serving-batch sizes and fully parallel."""
    R = keys.shape[0]
    i = jnp.arange(R, dtype=jnp.int32)
    same = (keys[None, :] == keys[:, None]) & mask[None, :]
    first_j = jnp.min(jnp.where(same, i[None, :], R), axis=1)
    return mask & (first_j == i)


def _compact(keys: jnp.ndarray, first: jnp.ndarray):
    """Pack the flagged keys to the front (first-appearance order).
    Returns (plan [R] int32 with -1 padding, count)."""
    R = keys.shape[0]
    pos = jnp.cumsum(first.astype(jnp.int32)) - 1
    idx = jnp.where(first, pos, R)            # R = out of bounds -> dropped
    plan = jnp.full((R,), -1, jnp.int32).at[idx].set(keys)
    return plan, jnp.sum(first.astype(jnp.int32))


def majority_stride(d: jnp.ndarray, n_d: jnp.ndarray):
    """Leap-style majority vote over the first ``n_d`` entries of the delta
    sequence ``d``: the dominant delta wins an absolute majority, else the
    most recent delta is the fallback (as in Leap).  Returns
    ``(stride, have)`` — ``have`` is False when there is no usable trend
    (no deltas, or a zero stride).  Shared by the core paging planner and
    the kvplane decode lookahead."""
    N = d.shape[0]
    dvalid = jnp.arange(N) < n_d
    same = (d[None, :] == d[:, None]) & dvalid[None, :]
    counts = jnp.where(dvalid, jnp.sum(same.astype(jnp.int32), axis=1), 0)
    best = jnp.argmax(counts).astype(jnp.int32)
    majority = counts[best] * 2 > n_d
    last = d[jnp.clip(n_d - 1, 0, N - 1)]
    stride = jnp.where(majority, d[best], last)
    return stride, (n_d >= 1) & (stride != 0)


class AccessPlan(NamedTuple):
    """Fixed-shape pytree describing one batch's ingress work.  Because the
    shapes depend only on the batch size (and the static prefetch budget),
    a sharded plane can compute the next batch's plan on host while the
    previous one executes.

    The paging section is fully resolved at plan time: ``pg_fetch`` lists
    every page-in to perform — the deduped demand misses followed by the
    prefetch-candidate section — and ``pg_victim`` pairs each with the
    frame it lands in (chosen by one masked top-k over the pool; a fetch
    with no usable victim is dropped to ``-1``).  The executors never make
    another eviction decision."""

    vpage: jnp.ndarray      # [R] entry vpages (soft-pin / recency targets)
    page_plan: jnp.ndarray  # [R] deduped paging-miss pages (-1 pad)
    n_pages: jnp.ndarray    # [] number of valid entries in page_plan
    obj_plan: jnp.ndarray   # [R] deduped runtime-miss objects (-1 pad)
    n_objs: jnp.ndarray     # [] number of valid entries in obj_plan
    pg_fetch: jnp.ndarray   # [R+Q] scheduled page-ins, demand++prefetch (-1)
    pg_victim: jnp.ndarray  # [R+Q] destination frame per scheduled fetch
    pg_is_pf: jnp.ndarray   # [R+Q] bool: entry belongs to the prefetch section
    # Fault-model section (repro.core.faults).  With no (or a null)
    # schedule: served == (obj_ids >= 0), n_miss == n_pages + n_objs and
    # n_failed == n_egress == 0 — every consumer below reduces to the
    # fault-free math.
    served: jnp.ndarray     # [R] bool: request's row is ground truth this tick
    n_miss: jnp.ndarray     # [] classified misses (pre-fault; stats basis)
    n_failed: jnp.ndarray   # [] planned fetches masked off by the fault model
    n_egress: jnp.ndarray   # [] remote writes blocked by the fault model
    #                         (eviction writebacks dropped at victim planning
    #                         + remote update writes masked when for_update)


def _prefetch_candidates(cfg: PlaneConfig, s: st.PlaneState,
                         page_plan: jnp.ndarray, n_pages: jnp.ndarray,
                         *, use_psf: bool) -> jnp.ndarray:
    """Build the prefetch-candidate section of the paging plan: ``[Q]``
    pages (-1 pad), deduped, bounds/backing checked, PSF-masked (hybrid
    only) and disjoint from the demand plan.

    ``prefetch="sequential"`` is the seed readahead policy in plan form:
    each demand miss contributes its following ``cfg.readahead`` pages, in
    (miss order, offset) priority.  ``prefetch="majority"`` is the
    Leap-style detector: a majority vote over the deltas of the deduped
    miss stream picks the dominant stride (falling back to the most recent
    delta when no majority exists, as in Leap), and candidates extrapolate
    that trend from the last miss."""
    V, Q, R = cfg.num_vpages, cfg.prefetch_budget, page_plan.shape[0]
    none = jnp.full((Q,), -1, jnp.int32)
    if cfg.prefetch == "sequential":
        if cfg.readahead <= 0:
            return none
        off = jnp.arange(1, cfg.readahead + 1, dtype=jnp.int32)
        cand = jnp.where(page_plan[:, None] >= 0,
                         page_plan[:, None] + off[None, :], -1).reshape(-1)
    else:  # "majority"
        if R < 2:
            return none
        stride, have = majority_stride(page_plan[1:] - page_plan[:-1],
                                       jnp.maximum(n_pages - 1, 0))
        base = page_plan[jnp.clip(n_pages - 1, 0, R - 1)]
        k = jnp.arange(1, Q + 1, dtype=jnp.int32)
        cand = jnp.where(have, base + k * stride, -1)
    ok = (cand >= 0) & (cand < V)
    safe = jnp.clip(cand, 0, V - 1)
    ok &= s.backing[safe] == REMOTE          # allocated and currently far
    if use_psf:
        ok &= s.psf[safe]                    # only paging-path pages
    ok &= ~jnp.any(cand[:, None] == page_plan[None, :], axis=1)
    cand = jnp.where(ok, cand, -1)
    plan, _ = _compact(cand, _first_of(cand, ok))
    return plan[:Q]


def _plan_victims(cfg: PlaneConfig, s: st.PlaneState, req_v: jnp.ndarray,
                  fetch: jnp.ndarray, is_pf: jnp.ndarray):
    """Pair every scheduled fetch with a destination frame in ONE masked
    top-k over the frame pool: free frames first (index order), then the
    coldest unpinned occupied frames by entry clock.  Frames holding this
    batch's target pages rank last (the soft-pin: evicted only under
    extreme pressure, and never for a prefetch); pinned frames never.
    Fetches beyond the usable pool are dropped (-1) — prefetches first,
    since the demand section precedes them in rank order."""
    F, V = cfg.num_frames, cfg.num_vpages
    N = fetch.shape[0]
    occ = s.vpage_of >= 0
    vres = jnp.maximum(s.vpage_of, 0)
    pinned = occ & (s.pin[vres] > 0)
    target = jnp.zeros((V,), bool).at[req_v].set(True)
    is_tgt = occ & target[vres]
    score = jnp.where(~occ, -INF32,
                      jnp.where(pinned, INF32,
                                jnp.where(is_tgt, INF32 - 1, s.clock[vres])))
    k = min(N, F)
    neg, victims = lax.top_k(-score, k)          # ascending-score frames
    vic_score = -neg
    ok = fetch >= 0
    rank = jnp.cumsum(ok.astype(jnp.int32)) - 1
    r = jnp.clip(rank, 0, k - 1)
    vs = vic_score[r]
    usable = ok & (rank < k) & (vs < INF32) & (~is_pf | (vs < INF32 - 1))
    return (jnp.where(usable, fetch, -1),
            jnp.where(usable, victims[r], -1))


def plan_access(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray,
                *, split_by_psf: bool = True, all_runtime: bool = False,
                degraded=False, for_update: bool = False,
                shard=None) -> AccessPlan:
    """Classify the batch and build the two ingress plans (plus the paging
    plan's prefetch section and victim assignment).

    Shape contract: ``obj_ids`` is ``[R]`` int32 (negative = padded no-op
    request); the returned :class:`AccessPlan` is the fixed-shape pytree
    above, every field a function of ``(cfg, state, obj_ids)`` only.
    Owned by DESIGN.md §3 (plan/execute split) and §6/§6c (fault masking).

    ``split_by_psf=False`` sends every miss down the paging plan (Fastswap
    baseline; its prefetch section skips the PSF mask — no PSF
    consultation is the point); ``all_runtime=True`` sends every miss down
    the runtime plan (AIFM baseline; no paging section at all).

    When ``cfg.faults`` is an active schedule, each planned remote fetch
    is additionally masked by ``faults.fetch_fail(step+1, vpage, shard)``
    — a faulted fetch becomes a ``-1`` no-op plan entry (the PR-4 padding
    convention), the requests that depended on it come back with
    ``served=False``, and ``n_failed`` counts the masked fetches.  Because
    the mask is applied at *plan* time, a faulted fetch never moves a
    byte: no victim is paged out for it and no frame is partially
    written.

    Egress faults apply the same plan-time discipline to remote *writes*
    (DESIGN.md §6c): a scheduled page-in whose victim frame holds a page
    that cannot be written back is dropped (fetch and victim to ``-1``,
    demand drops counted in ``n_egress``) — the occupant stays local, the
    requester still serves from the slab copy, nothing is lost.  With
    ``for_update=True`` (the write path), requests predicted to remain
    remote at execute time are additionally masked ``served=False`` when
    their slab write would fault, so ``execute_update`` mutates neither
    tier for them.

    ``degraded`` (the engine's circuit-breaker mode) suppresses every
    remote fetch instead — the plane serves local hits only, without
    charging ``fetch_failures``.  It accepts a static Python bool (one
    compiled program per mode) or a traced scalar bool (the sharded
    per-shard breaker passes each shard its own flag through one shared
    program); both produce bit-identical plans."""
    R = obj_ids.shape[0]
    Q = cfg.prefetch_budget
    # A negative id is a padded no-op request (the sharded exchange and any
    # partially-filled batch use this): it misses nothing, touches nothing,
    # and its result row is zero.  Sentinel indices (V for vpages) make its
    # scatters drop and keep every shape static.
    valid = obj_ids >= 0
    vaddr = s.obj_loc[jnp.maximum(obj_ids, 0)]
    v = vaddr // cfg.page_objs
    local = s.backing[v] == LOCAL
    if all_runtime:
        pg_mask = jnp.zeros_like(local)
        rt_mask = valid & ~local
    elif split_by_psf:
        psf = s.psf[v]
        pg_mask = valid & ~local & psf
        rt_mask = valid & ~local & ~psf
    else:
        pg_mask = valid & ~local
        rt_mask = jnp.zeros_like(local)
    v = jnp.where(valid, v, cfg.num_vpages)
    page_plan, n_pages = _compact(v, _first_of(v, pg_mask))
    obj_plan, n_objs = _compact(obj_ids, _first_of(obj_ids, rt_mask))
    # Capacity governor for the runtime plan: fresh log pages allocate with
    # pin-masked LRU eviction, so when standing pins (allocation cursors)
    # occupy almost the whole pool, an unbounded move list could force the
    # allocator to evict a pinned cursor with appends still pending — the
    # corruption the seed's "callers bound pins per batch" note waved away.
    # Cap the moves so every fresh-page allocation still finds an unpinned
    # victim; excess miss objects simply stay remote this batch (the final
    # gather serves them from the slab — results stay ground truth).
    occ_f = s.vpage_of >= 0
    pinned_frames = jnp.sum(
        (occ_f & (s.pin[jnp.maximum(s.vpage_of, 0)] > 0)).astype(jnp.int32))
    fill = s.fill_vpage
    free_slots = jnp.where(fill >= 0,
                           cfg.page_objs
                           - s.alloc_count[jnp.maximum(fill, 0)], 0)
    cap = free_slots + cfg.page_objs * jnp.maximum(
        cfg.num_frames - pinned_frames, 0)
    n_objs = jnp.minimum(n_objs, cap)
    obj_plan = jnp.where(jnp.arange(R) < n_objs, obj_plan, -1)
    if all_runtime:
        pf_plan = jnp.full((Q,), -1, jnp.int32)
    else:
        # candidates come from the *unmasked* compacted demand plan (the
        # stride vote reads its deltas); fault masking happens below
        pf_plan = _prefetch_candidates(cfg, s, page_plan, n_pages,
                                       use_psf=split_by_psf)
    # classified misses, before any fault masking: the stats basis (a
    # faulted request still missed — it just isn't served this tick)
    n_miss = n_pages + n_objs
    served = valid
    n_failed = jnp.zeros((), jnp.int32)
    n_egress = jnp.zeros((), jnp.int32)
    fc = cfg.faults
    tick = s.step + 1                        # the step this batch executes at
    shard_i = 0 if shard is None else shard
    static_deg = isinstance(degraded, bool)
    if static_deg and degraded:
        # circuit-breaker mode: attempt no remote fetch at all (demand,
        # object or speculative) — local hits are the whole service
        page_plan = jnp.full((R,), -1, jnp.int32)
        n_pages = jnp.zeros((), jnp.int32)
        obj_plan = jnp.full((R,), -1, jnp.int32)
        n_objs = jnp.zeros((), jnp.int32)
        pf_plan = jnp.full((Q,), -1, jnp.int32)
        served = valid & local
        egress_on = False                    # no remote write can be planned
    else:
        if fc is not None and fc.active:
            # demand paging plan: faulted entries hole out to -1 (the
            # executors' `fetch >= 0` masks drop holes without re-compaction)
            failp = (page_plan >= 0) & fc.fetch_fail(tick, page_plan, shard_i)
            n_failed_p = jnp.sum(failp.astype(jnp.int32))
            page_plan = jnp.where(failp, -1, page_plan)
            n_pages = n_pages - n_failed_p
            # speculative fetches fault too, but silently (not a failure: no
            # request depended on them)
            failq = (pf_plan >= 0) & fc.fetch_fail(tick, pf_plan, shard_i)
            pf_plan = jnp.where(failq, -1, pf_plan)
            # runtime plan: mask, then RE-compact — _exec_runtime assigns
            # append slots positionally (`t < n_move`), so holes are not
            # allowed
            v_obj = s.obj_loc[jnp.maximum(obj_plan, 0)] // cfg.page_objs
            failo = (obj_plan >= 0) & fc.fetch_fail(tick, v_obj, shard_i)
            n_failed_o = jnp.sum(failo.astype(jnp.int32))
            keep = (obj_plan >= 0) & ~failo
            obj_plan, n_objs = _compact(jnp.where(keep, obj_plan, -1), keep)
            # a request is served unless its (remote) page's fetch faulted;
            # capacity-capped and victim-starved requests still serve from
            # the written-back slab copy (memory pressure, not a fault)
            served = valid & (local | ~fc.fetch_fail(tick, v, shard_i))
            n_failed = n_failed_p + n_failed_o
        if not static_deg:
            # traced circuit-breaker flag (the sharded per-shard breaker):
            # emulate the static degraded branch with where-overrides so one
            # compiled program serves degraded and healthy shards alike,
            # bit-identically to the static branch per shard
            deg = jnp.asarray(degraded, bool)
            page_plan = jnp.where(deg, -1, page_plan)
            n_pages = jnp.where(deg, 0, n_pages)
            obj_plan = jnp.where(deg, -1, obj_plan)
            n_objs = jnp.where(deg, 0, n_objs)
            pf_plan = jnp.where(deg, -1, pf_plan)
            served = jnp.where(deg, valid & local, served)
            n_failed = jnp.where(deg, 0, n_failed)
        egress_on = fc is not None and fc.egress_active
    fetch = jnp.concatenate([page_plan, pf_plan])
    is_pf = jnp.concatenate([jnp.zeros((R,), bool), jnp.ones((Q,), bool)])
    fetch, victim = _plan_victims(cfg, s, v, fetch, is_pf)
    if egress_on:
        # egress side (DESIGN.md §6c): a scheduled page-in whose victim
        # frame holds a page that cannot be written back this tick is
        # dropped whole — the occupant stays local (no data loss), the
        # requester still serves from the slab copy.  Keyed by the
        # *occupant* vpage: the write that would fail is its writeback.
        old_v = s.vpage_of[jnp.maximum(victim, 0)]
        evicting = (victim >= 0) & (old_v >= 0)
        efail = evicting & fc.egress_fail(tick, jnp.maximum(old_v, 0),
                                          shard_i)
        n_egress = jnp.sum((efail & ~is_pf).astype(jnp.int32))
        fetch = jnp.where(efail, -1, fetch)
        victim = jnp.where(efail, -1, victim)
        if for_update:
            # the write path: a request predicted to remain remote at
            # execute time writes the slab — mask it unserved when that
            # write would fault, so execute_update touches nothing for it
            # (conservative prediction: extreme-pressure mid-batch
            # evictions can only flip a predicted-local entry to an
            # unmasked slab write, which stays correct, just unfaulted)
            will_local = local | jnp.any(
                (fetch[None, :] == v[:, None]) & (victim[None, :] >= 0),
                axis=1)
            moved = jnp.any((obj_plan[None, :] == obj_ids[:, None])
                            & (obj_plan[None, :] >= 0), axis=1)
            wfail = (served & ~will_local & ~moved
                     & fc.egress_fail(tick, v, shard_i))
            served = served & ~wfail
            n_egress = n_egress + jnp.sum(wfail.astype(jnp.int32))
    return AccessPlan(v, page_plan, n_pages, obj_plan, n_objs,
                      fetch, victim, is_pf, served, n_miss, n_failed,
                      n_egress)


# --------------------------------------------------------------------------
# execution: paging plan
# --------------------------------------------------------------------------

def _exec_paging(cfg: PlaneConfig, s: st.PlaneState, plan: AccessPlan, *,
                 scalar: bool) -> st.PlaneState:
    """Execute the planned page-ins (demand + prefetch).

    The batched executor performs every page-out as masked scatters
    (write-back, PSF-from-CAR, CAT clear) and every page-in with ONE
    ``kernels.gather_pages`` call over the slab's page view, then one
    frame-pool scatter — no per-victim ``fori_loop``/``cond`` chain.  Safe
    because the plan's touched sets are disjoint: victims are distinct
    frames (top-k), evicted pages are currently resident, fetched pages
    are currently remote.  The scalar executor replays the identical plan
    one fetch at a time through the ``paths`` helpers — the equivalence
    oracle, bit-identical by the same disjointness."""
    P, V, F, D = cfg.page_objs, cfg.num_vpages, cfg.num_frames, cfg.obj_dim
    fetch, vic, is_pf = plan.pg_fetch, plan.pg_victim, plan.pg_is_pf
    N = fetch.shape[0]
    ok = fetch >= 0

    if scalar:
        def body(j, s):
            def do(s):
                f = vic[j]
                s = lax.cond(s.vpage_of[f] >= 0,
                             lambda s: paths.page_out(cfg, s, f),
                             lambda s: s, s)
                s = paths.page_in_at(cfg, s, fetch[j], f)

                def mark(s):
                    return s._replace(
                        prefetched=s.prefetched.at[fetch[j]].set(True),
                        stats=st.bump(s.stats, prefetch_issued=1))

                return lax.cond(is_pf[j], mark, lambda s: s, s)

            return lax.cond(ok[j], do, lambda s: s, s)

        return lax.fori_loop(0, N, body, s)

    # ---- page-out: masked scatters over the distinct victim set ---------
    vf = jnp.maximum(vic, 0)
    old_v = jnp.where(ok, s.vpage_of[vf], -1)
    evict = ok & (old_v >= 0)
    ovs = jnp.maximum(old_v, 0)
    ov = jnp.where(evict, old_v, V)              # OOB scatter index = drop
    car_inst = (jnp.sum(s.cat[ovs].astype(jnp.int32), axis=1).astype(
        jnp.float32) / jnp.maximum(s.alloc_count[ovs], 1).astype(jnp.float32))
    car = jnp.maximum(car_inst, s.car_ema[ovs])  # EMA blend (see paths.page_out)
    new_psf = car >= s.car_thr
    old_psf = s.psf[ovs]
    flip_p = jnp.sum((evict & ~old_psf & new_psf).astype(jnp.int32))
    flip_r = jnp.sum((evict & old_psf & ~new_psf).astype(jnp.int32))
    n_dirty = jnp.sum((evict & s.dirty[ovs]).astype(jnp.int32))
    slab = s.slab.at[ov].set(s.frames[vf])       # unconditional write-back
    psf = s.psf.at[ov].set(new_psf)
    cat = s.cat.at[ov].set(False)
    backing = s.backing.at[ov].set(jnp.int8(REMOTE))
    frame_of = s.frame_of.at[ov].set(-1)
    dirty = s.dirty.at[ov].set(False)
    prefetched = s.prefetched.at[ov].set(False)  # unread prefetch wasted

    # ---- page-in: ONE batched gather over the slab page view ------------
    vin = jnp.where(ok, fetch, V)
    pages = kops.gather_pages(slab[None], jnp.where(ok, fetch, -1),
                              impl=cfg.kernel_impl, masked=False)[0]
    fdst = jnp.where(ok, vic, F)
    frames = s.frames.at[fdst].set(pages)
    backing = backing.at[vin].set(jnp.int8(LOCAL))
    frame_of = frame_of.at[vin].set(vic)
    vpage_of = s.vpage_of.at[fdst].set(jnp.where(ok, fetch, -1))
    cat = cat.at[vin].set(False)
    clock = s.clock.at[vin].set(s.step)
    prefetched = prefetched.at[vin].set(is_pf)
    return s._replace(
        slab=slab, frames=frames, backing=backing, frame_of=frame_of,
        vpage_of=vpage_of, cat=cat, psf=psf, dirty=dirty, clock=clock,
        prefetched=prefetched,
        stats=st.bump(
            s.stats,
            page_ins=jnp.sum(ok.astype(jnp.int32)),
            page_outs=jnp.sum(evict.astype(jnp.int32)),
            dirty_page_outs=n_dirty, psf_to_paging=flip_p,
            psf_to_runtime=flip_r,
            prefetch_issued=jnp.sum((ok & is_pf).astype(jnp.int32))))


def _account_prefetch_hits(cfg: PlaneConfig, s: st.PlaneState,
                           plan: AccessPlan) -> st.PlaneState:
    """Coverage accounting against batch-entry state: a demand access to a
    page whose ``prefetched`` bit is standing means that prefetch turned a
    would-be miss into a hit.  Mode-independent (pure vectorized), so both
    executors agree."""
    used = (jnp.zeros((cfg.num_vpages,), bool).at[plan.vpage].set(True)
            & s.prefetched)
    n_used = jnp.sum(used.astype(jnp.int32))
    return s._replace(prefetched=s.prefetched & ~used,
                      stats=st.bump(s.stats, prefetch_used=n_used))


# --------------------------------------------------------------------------
# execution: runtime plan
# --------------------------------------------------------------------------

def _exec_runtime(cfg: PlaneConfig, s: st.PlaneState, obj_plan: jnp.ndarray,
                  n_move: jnp.ndarray, *, scalar: bool) -> st.PlaneState:
    """Move the deduped miss objects onto the ingress fill page(s).

    The append-slot of every object is computed up front with prefix
    arithmetic over the fill cursor; fresh log pages are allocated before
    any row moves (so allocation can never page out a page that still has
    pending appends).  The batched executor then fetches all rows with one
    ``gather_rows`` call and scatters them into the frame pool; the scalar
    executor replays the same plan one row at a time."""
    P, V, F, O = cfg.page_objs, cfg.num_vpages, cfg.num_frames, cfg.num_objs
    R, D = obj_plan.shape[0], cfg.obj_dim

    # ---- fill-capacity plan (prefix arithmetic over the cursor state)
    cur0 = s.fill_vpage
    have = cur0 >= 0
    a0 = jnp.where(have, s.alloc_count[jnp.maximum(cur0, 0)], P)
    free0 = P - a0                       # free slots on the current cursor
    use0 = jnp.minimum(n_move, free0)
    overflow = n_move - use0
    n_fresh = (overflow + P - 1) // P    # fresh log pages needed
    MAXF = (R + P - 1) // P + 1          # static bound

    def alloc_body(j, carry):
        s, fresh = carry
        s, v = paths._fresh_vpage(cfg, s)        # pinned on allocation
        return s, fresh.at[j].set(v)

    fresh0 = jnp.full((MAXF,), -1, jnp.int32)
    s, fresh = lax.fori_loop(0, n_fresh, alloc_body, (s, fresh0))

    # ---- destination of move t: cursor first, then fresh pages in order
    t = jnp.arange(R, dtype=jnp.int32)
    valid = t < n_move
    tt = t - use0
    in_cur = t < use0
    v_new = jnp.where(in_cur, jnp.maximum(cur0, 0),
                      fresh[jnp.clip(tt // P, 0, MAXF - 1)])
    v_new = jnp.where(valid, v_new, 0)
    slot_new = jnp.where(valid, jnp.where(in_cur, a0 + t, tt % P), 0)

    o = jnp.maximum(obj_plan, 0)
    old = s.obj_loc[o]
    v_old, slot_old = old // P, old % P

    if scalar:
        def move_body(k, s):
            f_new = s.frame_of[v_new[k]]
            row = s.slab[v_old[k], slot_old[k]]
            s = s._replace(
                frames=s.frames.at[f_new, slot_new[k]].set(row),
                obj_loc=s.obj_loc.at[o[k]].set(v_new[k] * P + slot_new[k]),
                obj_of=s.obj_of.at[v_new[k], slot_new[k]].set(o[k]),
                alloc_count=s.alloc_count.at[v_new[k]].add(1),
                live_count=s.live_count.at[v_new[k]].add(1),
                cat=s.cat.at[v_new[k], slot_new[k]].set(True),
            )
            return paths._kill_old_copy(cfg, s, v_old[k], slot_old[k])

        s = lax.fori_loop(0, n_move, move_body, s)
    else:
        # one batched gather (the Pallas object-ingress kernel on TPU) ...
        src_flat = jnp.where(valid, v_old * P + slot_old, -1)
        rows = kops.gather_rows(s.slab.reshape(V * P, D), src_flat,
                                impl=cfg.kernel_impl)
        # ... and one batched scatter into the frame pool
        f_dst = jnp.where(valid, s.frame_of[v_new] * P + slot_new, F * P)
        frames = s.frames.reshape(F * P, D).at[f_dst].set(rows)

        dst_flat = jnp.where(valid, v_new * P + slot_new, V * P)
        old_flat = jnp.where(valid, v_old * P + slot_old, V * P)
        v_new_m = jnp.where(valid, v_new, V)
        v_old_m = jnp.where(valid, v_old, V)
        obj_of = s.obj_of.reshape(V * P).at[dst_flat].set(o)
        obj_of = obj_of.at[old_flat].set(-1)
        live = s.live_count.at[v_new_m].add(1).at[v_old_m].add(-1)
        s = s._replace(
            frames=frames.reshape(F, P, D),
            obj_loc=s.obj_loc.at[jnp.where(valid, o, O)].set(
                v_new * P + slot_new),
            obj_of=obj_of.reshape(V, P),
            alloc_count=s.alloc_count.at[v_new_m].add(1),
            live_count=live,
            # bool tables scatter in 2-D: a flat [V*P] bool scatter takes the
            # TPU compiler minutes at 1 Mi records
            cat=s.cat.at[v_new_m, slot_new].set(True),
        )
        # GC source pages this batch fully drained (deferred equivalent of
        # the scalar path's per-move _kill_old_copy)
        touched = jnp.zeros((V,), bool).at[v_old_m].set(True)
        drained = touched & (s.live_count == 0) & (s.pin == 0)
        s = s._replace(
            backing=jnp.where(drained, jnp.int8(FREE), s.backing),
            dirty=jnp.where(drained, False, s.dirty),
        )

    # ---- cursor bookkeeping: the last fresh page becomes the fill cursor;
    # the retired cursor and intermediate (already-full) fresh pages unpin
    retired = (n_fresh > 0) & have
    pin = s.pin.at[jnp.where(retired, jnp.maximum(cur0, 0), V)].add(-1)
    j = jnp.arange(MAXF)
    interm = jnp.where(j < n_fresh - 1, jnp.maximum(fresh, 0), V)
    pin = pin.at[interm].add(-1)
    new_cursor = jnp.where(n_fresh > 0,
                           fresh[jnp.clip(n_fresh - 1, 0, MAXF - 1)], cur0)
    return s._replace(pin=pin, fill_vpage=new_cursor,
                      stats=st.bump(s.stats, obj_ins=n_move))


# --------------------------------------------------------------------------
# finish: profiling pass + batched result gather
# --------------------------------------------------------------------------

def _profile(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray, *,
             with_cat: bool, with_obj_last: bool, scalar: bool
             ) -> st.PlaneState:
    """Record every access at its *final* location in one vectorized pass
    (cat_update-style: duplicate touches OR together, no scatter hazards).
    Padded (negative-id) requests profile nothing: their scatter indices
    are out of bounds, so both executors drop them identically."""
    valid = obj_ids >= 0
    va = s.obj_loc[jnp.maximum(obj_ids, 0)]
    v, slot = va // cfg.page_objs, va % cfg.page_objs
    v = jnp.where(valid, v, cfg.num_vpages)
    oid = jnp.where(valid, obj_ids, cfg.num_objs)
    if scalar:
        def body(i, s):
            if with_cat:
                s = paths.touch(cfg, s, v[i], slot[i],
                                obj_id=oid[i] if with_obj_last else None)
            else:
                s = s._replace(clock=s.clock.at[v[i]].set(s.step))
                if with_obj_last:
                    s = s._replace(obj_last=s.obj_last.at[oid[i]].set(s.step))
            return s

        return lax.fori_loop(0, obj_ids.shape[0], body, s)
    if with_cat:
        s = s._replace(cat=s.cat.at[v, slot].set(True),
                       access=s.access.at[v, slot].set(True))
    s = s._replace(clock=s.clock.at[v].set(s.step))
    if with_obj_last:
        s = s._replace(obj_last=s.obj_last.at[oid].set(s.step))
    return s


def _gather_final(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray,
                  *, scalar: bool) -> jnp.ndarray:
    """Read every requested row at its final location with one batched
    gather per tier.  Under extreme pressure a target can be paged out
    again mid-batch; its row is then served from the written-back slab
    copy, so the result is ground truth either way.  Padded (negative-id)
    requests read as zero rows in both executors."""
    P, V, F, D = cfg.page_objs, cfg.num_vpages, cfg.num_frames, cfg.obj_dim
    valid = obj_ids >= 0
    va = s.obj_loc[jnp.maximum(obj_ids, 0)]
    v, slot = va // P, va % P
    local = s.backing[v] == LOCAL
    if scalar:
        R = obj_ids.shape[0]
        out = jnp.zeros((R, D), cfg.dtype)

        def body(i, out):
            row = jnp.where(local[i],
                            s.frames[jnp.maximum(s.frame_of[v[i]], 0), slot[i]],
                            s.slab[v[i], slot[i]])
            return lax.dynamic_update_index_in_dim(out, row, i, axis=0)

        out = lax.fori_loop(0, R, body, out)
        return jnp.where(valid[:, None], out, jnp.zeros_like(out))
    fidx = jnp.where(local, jnp.maximum(s.frame_of[v], 0) * P + slot, -1)
    sidx = jnp.where(local, -1, v * P + slot)
    rows_l = kops.gather_rows(s.frames.reshape(F * P, D), fidx,
                              impl=cfg.kernel_impl)
    rows_r = kops.gather_rows(s.slab.reshape(V * P, D), sidx,
                              impl=cfg.kernel_impl)
    rows = jnp.where(local[:, None], rows_l, rows_r)
    return jnp.where(valid[:, None], rows, jnp.zeros_like(rows))


# --------------------------------------------------------------------------
# the engine entry points
# --------------------------------------------------------------------------

def _resolve(cfg: PlaneConfig, mode) -> bool:
    mode = mode or cfg.access_mode
    if mode not in ("batch", "reference"):
        raise ValueError(f"unknown access mode: {mode!r}")
    return mode == "reference"


def execute_access(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray,
                   plan: AccessPlan, *, mode: str | None = None):
    """Execute a precomputed ``AccessPlan``: both ingress paths, profiling,
    final gather.

    Shape contract: ``obj_ids`` is ``[R]`` int32 (negative = padded no-op);
    returns ``(state, rows[R, D])`` with zero rows for padded or unserved
    requests.  Determinism invariant: ``mode="batch"`` and
    ``mode="reference"`` replay the *same* plan and produce bit-identical
    states and rows (tests/test_batch_equivalence.py), with or without an
    active fault schedule — the plan already decided every byte that moves.

    This is the second half of ``access``; the serving engine dispatches
    ``plan_access`` and ``execute_access`` as separate device calls so the
    host can enqueue batch N+1's plan while batch N's execute is still
    running (plan shapes depend only on the batch size — DESIGN.md §3b)."""
    scalar = _resolve(cfg, mode)
    nv = jnp.sum((obj_ids >= 0).astype(jnp.int32))   # padded ids don't count
    s = s._replace(step=s.step + 1)
    s = s._replace(stats=st.bump(s.stats, hits=nv - plan.n_miss,
                                 misses=plan.n_miss,
                                 fetch_failures=plan.n_failed,
                                 egress_failures=plan.n_egress))
    # pre-scope barrier analogue: refresh the recency of every target page
    # so mid-batch eviction prefers non-target pages (soft pin; the hard
    # deref-count pins stay host-side, see sync.py).  Unserved (faulted)
    # requests touched nothing — they profile as if padded.
    pids = jnp.where(plan.served, obj_ids, -1)
    s = s._replace(clock=s.clock.at[
        jnp.where(plan.served, plan.vpage, cfg.num_vpages)].set(s.step))
    s = _account_prefetch_hits(cfg, s, plan)
    s = _exec_paging(cfg, s, plan, scalar=scalar)
    s = _exec_runtime(cfg, s, plan.obj_plan, plan.n_objs, scalar=scalar)
    s = _profile(cfg, s, pids, with_cat=True, with_obj_last=True,
                 scalar=scalar)
    rows = _gather_final(cfg, s, pids, scalar=scalar)
    return s, rows


def access(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray, *,
           mode: str | None = None, shard=None, degraded: bool = False):
    """Batched hybrid access: plan, execute both ingress paths, profile,
    gather.  Returns ``(state, rows[R, D])``."""
    return execute_access(
        cfg, s, obj_ids,
        plan_access(cfg, s, obj_ids, shard=shard, degraded=degraded),
        mode=mode)


def update(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray,
           rows: jnp.ndarray, *, mode: str | None = None, shard=None,
           degraded=False) -> st.PlaneState:
    """Batched write-through-local: fault in, overwrite rows (last write
    wins for duplicate ids), mark dirty.  An unserved (fault-masked)
    request writes nothing — neither tier mutates, so a retry later sees
    the pre-fault value (no partial writes).  ``for_update=True`` extends
    that discipline to egress faults: a request whose row would have to be
    written to the remote slab is masked unserved when that write would
    fault (DESIGN.md §6c).

    The plan is built against pre-step state (``plan_access`` never reads
    ``s.step`` itself, so this matches the access path, where the serving
    engine plans one device call ahead of the step increment — keeps the
    fault-model tick stream identical across access and update)."""
    plan = plan_access(cfg, s, obj_ids, shard=shard, degraded=degraded,
                       for_update=True)
    return execute_update(cfg, s, obj_ids, rows, plan, mode=mode)


def execute_update(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray,
                   rows: jnp.ndarray, plan: AccessPlan, *,
                   mode: str | None = None) -> st.PlaneState:
    """Execute a precomputed write-through plan: the second half of
    ``update``, split out (like ``plan_access``/``execute_access``) so the
    sharded exchange can interleave a round's plan and execute with the
    neighbouring rounds' collectives (repro.core.shardplane)."""
    scalar = _resolve(cfg, mode)
    P, V, F = cfg.page_objs, cfg.num_vpages, cfg.num_frames
    R = obj_ids.shape[0]
    rows = rows.astype(cfg.dtype)
    s = s._replace(step=s.step + 1)
    valid = obj_ids >= 0
    nv = jnp.sum(valid.astype(jnp.int32))
    s = s._replace(stats=st.bump(s.stats, hits=nv - plan.n_miss,
                                 misses=plan.n_miss,
                                 fetch_failures=plan.n_failed,
                                 egress_failures=plan.n_egress))
    served = plan.served
    pids = jnp.where(served, obj_ids, -1)
    s = s._replace(clock=s.clock.at[
        jnp.where(served, plan.vpage, V)].set(s.step))
    s = _account_prefetch_hits(cfg, s, plan)
    s = _exec_paging(cfg, s, plan, scalar=scalar)
    s = _exec_runtime(cfg, s, plan.obj_plan, plan.n_objs, scalar=scalar)
    s = _profile(cfg, s, pids, with_cat=True, with_obj_last=True,
                 scalar=scalar)

    va = s.obj_loc[jnp.maximum(obj_ids, 0)]
    v, slot = va // P, va % P
    local = s.backing[v] == LOCAL
    # padded (negative-id) and unserved (faulted) requests write nothing:
    # sentinel indices drop, so a failed write never mutates either tier
    vw = jnp.where(served, v, V)
    if scalar:
        def body(i, s):
            def to_frames(s):
                f = jnp.maximum(s.frame_of[v[i]], 0)
                return s._replace(
                    frames=s.frames.at[f, slot[i]].set(rows[i]),
                    dirty=s.dirty.at[v[i]].set(True))

            def to_slab(s):
                return s._replace(slab=s.slab.at[vw[i], slot[i]].set(rows[i]))

            return lax.cond(served[i] & local[i], to_frames, to_slab, s)

        return lax.fori_loop(0, R, body, s)

    # last-wins dedup for duplicate ids, then one scatter per tier
    i = jnp.arange(R, dtype=jnp.int32)
    same = (obj_ids[None, :] == obj_ids[:, None])
    last = (jnp.max(jnp.where(same, i[None, :], -1), axis=1) == i) & served
    fidx = jnp.where(last & local, jnp.maximum(s.frame_of[v], 0) * P + slot,
                     F * P)
    sidx = jnp.where(last & ~local, v * P + slot, V * P)
    D = cfg.obj_dim
    return s._replace(
        frames=s.frames.reshape(F * P, D).at[fidx].set(rows).reshape(F, P, D),
        slab=s.slab.reshape(V * P, D).at[sidx].set(rows).reshape(
            cfg.num_vpages, P, D),
        dirty=s.dirty.at[jnp.where(served & local, v, V)].set(True),
    )


# --------------------------------------------------------------------------
# evacuation append-stream planning (used by plane.evacuate)
# --------------------------------------------------------------------------

def plan_append_stream(cfg: PlaneConfig, s: st.PlaneState, which: str,
                       mask: jnp.ndarray):
    """Plan appending the masked slots of one page to the named fill stream.

    ``mask`` is a [P] bool of source slots (so at most one fresh page is
    ever needed).  Allocates that fresh page up front (pinned), updates the
    stream cursor and the destination alloc/live counts, and returns
    ``(state, v_new[P], slot_new[P], in_cur[P], cursor_page, fresh_page,
    retired_page)`` where the destination arrays are only meaningful where
    ``mask`` holds and ``in_cur`` says whether a slot lands on the
    pre-existing cursor page (vs the fresh page).

    A cursor that fills up retires, but it is NOT unpinned here: its
    destination slots have not been written yet, and a later allocation
    (the other evacuation stream's fresh page) could otherwise pick the
    unpinned page as an eviction victim while writes are pending.  The
    caller must unpin ``retired_page`` (when >= 0) after the data
    movement lands."""
    P, V = cfg.page_objs, cfg.num_vpages
    n = jnp.sum(mask.astype(jnp.int32))
    cur0 = getattr(s, which)
    have = cur0 >= 0
    a0 = jnp.where(have, s.alloc_count[jnp.maximum(cur0, 0)], P)
    free0 = P - a0
    use0 = jnp.minimum(n, free0)
    need_fresh = n > free0

    s, vfresh = lax.cond(
        need_fresh,
        lambda s: paths._fresh_vpage(cfg, s),
        lambda s: (s, jnp.asarray(-1, jnp.int32)), s)

    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    in_cur = rank < use0
    v_new = jnp.where(in_cur, jnp.maximum(cur0, 0), jnp.maximum(vfresh, 0))
    slot_new = jnp.where(in_cur, a0 + rank, rank - use0)

    vm = jnp.where(mask, v_new, V)
    s = s._replace(alloc_count=s.alloc_count.at[vm].add(1),
                   live_count=s.live_count.at[vm].add(1))
    # cursor bookkeeping: a filled cursor retires (deferred unpin, see above)
    retired_page = jnp.where(need_fresh & have, cur0, -1)
    new_cur = jnp.where(need_fresh, vfresh, cur0)
    s = s._replace(**{which: new_cur})
    used_cur = jnp.where(use0 > 0, cur0, -1)
    return s, v_new, slot_new, in_cur, used_cur, vfresh, retired_page


# --------------------------------------------------------------------------
# baseline planes on the same engine
# --------------------------------------------------------------------------

def execute_paging_access(cfg: PlaneConfig, s: st.PlaneState,
                          obj_ids: jnp.ndarray, plan: AccessPlan, *,
                          mode: str | None = None):
    """Execute a Fastswap-analogue plan (built with ``split_by_psf=False``:
    every miss takes the paging path; no CAT, no object moves)."""
    scalar = _resolve(cfg, mode)
    nv = jnp.sum((obj_ids >= 0).astype(jnp.int32))
    s = s._replace(step=s.step + 1)
    s = s._replace(stats=st.bump(s.stats, hits=nv - plan.n_miss,
                                 misses=plan.n_miss,
                                 fetch_failures=plan.n_failed,
                                 egress_failures=plan.n_egress))
    pids = jnp.where(plan.served, obj_ids, -1)
    # page-level recency only (no card profiling — that's the point)
    s = s._replace(clock=s.clock.at[
        jnp.where(plan.served, plan.vpage, cfg.num_vpages)].set(s.step))
    s = _account_prefetch_hits(cfg, s, plan)
    s = _exec_paging(cfg, s, plan, scalar=scalar)
    rows = _gather_final(cfg, s, pids, scalar=scalar)
    return s, rows


def paging_access(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray,
                  *, mode: str | None = None, shard=None,
                  degraded: bool = False):
    """Fastswap-analogue plane on the batch engine."""
    plan = plan_access(cfg, s, obj_ids, split_by_psf=False, shard=shard,
                       degraded=degraded)
    return execute_paging_access(cfg, s, obj_ids, plan, mode=mode)


def execute_object_access(cfg: PlaneConfig, s: st.PlaneState,
                          obj_ids: jnp.ndarray, plan: AccessPlan,
                          reclaim_free_target: int = 2, *,
                          mode: str | None = None, reclaim=None):
    """Execute an AIFM-analogue plan (built with ``all_runtime=True``:
    every miss object-fetches through the runtime plan); afterwards the
    caller-supplied ``reclaim`` (the object-level LRU egress loop) runs if
    frames are tight."""
    scalar = _resolve(cfg, mode)
    nv = jnp.sum((obj_ids >= 0).astype(jnp.int32))
    s = s._replace(step=s.step + 1)
    s = s._replace(stats=st.bump(s.stats, hits=nv - plan.n_miss,
                                 misses=plan.n_miss,
                                 fetch_failures=plan.n_failed,
                                 egress_failures=plan.n_egress))
    pids = jnp.where(plan.served, obj_ids, -1)
    s = s._replace(clock=s.clock.at[
        jnp.where(plan.served, plan.vpage, cfg.num_vpages)].set(s.step))
    s = _exec_runtime(cfg, s, plan.obj_plan, plan.n_objs, scalar=scalar)
    # object-level hotness tracking (the expensive always-on metadata)
    s = _profile(cfg, s, pids, with_cat=False, with_obj_last=True,
                 scalar=scalar)
    rows = _gather_final(cfg, s, pids, scalar=scalar)
    if reclaim is not None:
        s = reclaim(cfg, s, reclaim_free_target)
    return s, rows


def object_access(cfg: PlaneConfig, s: st.PlaneState, obj_ids: jnp.ndarray,
                  reclaim_free_target: int = 2, *, mode: str | None = None,
                  reclaim=None, shard=None, degraded: bool = False):
    """AIFM-analogue plane on the batch engine."""
    plan = plan_access(cfg, s, obj_ids, all_runtime=True, shard=shard,
                       degraded=degraded)
    return execute_object_access(cfg, s, obj_ids, plan, reclaim_free_target,
                                 mode=mode, reclaim=reclaim)
