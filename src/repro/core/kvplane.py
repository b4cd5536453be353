"""Production tiered KV cache on the Atlas plane (the serve-path fast path).

Two modes, matching the two ends of the paper's spectrum:

* **dense paging mode** (decode_32k): the whole cache fits local; KV lives
  in a paged frame pool indirected by a page table (vLLM-style).  Dense
  decode attention touches every token -> every card bit sets -> CAR = 1 ->
  all pages stay on the paging path.  The always-on CAT profiling still
  runs (its cost is part of what we benchmark).

* **sparse hybrid mode** (long_500k): frames hold only a hot subset of
  pages; the rest live in the far tier (slab).  Each step:
    1. page summaries (kmax/kmin) are scored against q *without fetching*
       (offload-space computation, `kernels.topk_pages`);
    2. the top-k pages are ensured local by the plan-then-execute fetch
       engine: ONE vectorized plan for the whole [B, K] selection
       (``plan_fetch``: per-seq miss ranking, cross-seq dedup, eviction
       victims in a single masked top-k over the shared pool), then all
       page-ins in one batched ``kernels.gather_pages`` call — PSF=paging
       pages arrive whole (bulk DMA), PSF=runtime pages packed to their
       CAT-marked hot rows.  ``fetch_mode="reference"`` replays the
       identical plan through the seed-era scalar loop (the equivalence
       oracle, see tests/test_batch_equivalence.py);
    3. paged flash attention runs over the local pool;
    4. CAT bits are set for the attended rows, and an evicted page's PSF
       is recomputed from CAR at page-out.

The serve loop should enter through ``jitted_attend_sparse`` /
``jitted_sharded_decode``: memoized jit entry points that DONATE the plane
state, so the (huge, unmodified) slab buffers alias through the step
instead of being copied every call.

Everything is static-shaped and vectorized: this is the form of the hybrid
plane that lowers into the multi-pod dry-run.  The fully dynamic
(fault-driven) plane lives in ``repro.core.plane`` and backs the
benchmarks; both implement the same policy.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import batch as batch_lib
from repro.kernels import ops

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class KVPlaneConfig:
    kv_heads: int
    head_dim: int
    page_tokens: int          # P: tokens per page
    num_pages: int            # NP: logical pages (covers max seq len)
    num_frames: int           # F: local frame pool (== B*NP in dense mode)
    batch: int                # sequences served per shard
    sparse_topk: int = 0      # 0 = dense paging mode; >0 = hybrid sparse
    fetch_budget: int = 8     # pages ensured local per step (sparse mode)
    car_threshold: float = 0.8
    dtype: object = jnp.bfloat16
    # plan-then-execute fetch engine (mirrors PlaneConfig.access_mode):
    fetch_mode: str = "batch"   # "batch" (vectorized) | "reference" (scalar)
    kernel_impl: str = "auto"   # kernels.ops dispatch for the batched movers
    # decode lookahead (mirrors PlaneConfig.prefetch): extend the fetch plan
    # with pages the top-page trajectory is trending toward
    prefetch: str = "none"      # "none" | "sequential" | "majority"
    prefetch_budget: int = 0    # lookahead pages planned per sequence
    # fault model (repro.core.faults.Schedule; None == null schedule):
    # faulted fetches drop out of the plan before victim assignment, so
    # attention proceeds on whatever is resident (graceful degradation)
    faults: object = None

    @property
    def dense(self) -> bool:
        return self.sparse_topk == 0

    @property
    def plan_entries(self) -> int:
        """Fetch-plan length: demand budget + lookahead, per sequence."""
        pf = self.prefetch_budget if self.prefetch != "none" else 0
        return self.batch * (self.fetch_budget + pf)


class KVPlaneState(NamedTuple):
    """Per-layer state (callers stack a leading layer axis and scan)."""
    k_frames: jnp.ndarray   # [KVH, F, P, Dh]
    v_frames: jnp.ndarray   # [KVH, F, P, Dh]
    page_table: jnp.ndarray # [B, NP] int32: logical page -> frame (-1 far)
    # --- far tier + profiling (sparse mode; size-1 placeholders in dense)
    k_slab: jnp.ndarray     # [KVH, B*NP, P, Dh]
    v_slab: jnp.ndarray     # [KVH, B*NP, P, Dh]
    kmax: jnp.ndarray       # [KVH, B*NP, Dh] page summaries (always local)
    kmin: jnp.ndarray       # [KVH, B*NP, Dh]
    cat: jnp.ndarray        # [B, NP, P] bool
    psf: jnp.ndarray        # [B, NP] bool
    hot_hint: jnp.ndarray   # [B, NP, P] bool: CAT snapshot from last residency
    page_rows: jnp.ndarray  # [B, NP] int32: valid rows in the frame copy
    frame_page: jnp.ndarray # [F] int32: frame -> b*NP+page (-1 free)
    clock: jnp.ndarray      # [F] int32
    step: jnp.ndarray       # [] int32


def init(cfg: KVPlaneConfig) -> KVPlaneState:
    KVH, F, P, Dh, B, NP = (cfg.kv_heads, cfg.num_frames, cfg.page_tokens,
                            cfg.head_dim, cfg.batch, cfg.num_pages)
    dense = cfg.dense
    slab_pages = 1 if dense else B * NP
    if dense:
        # fully resident: page (b, j) -> frame b*NP + j
        pt = (jnp.arange(B)[:, None] * NP + jnp.arange(NP)[None, :]).astype(
            jnp.int32)
        frame_page = jnp.arange(B * NP, dtype=jnp.int32)
        assert F == B * NP, "dense mode: frames must cover the cache"
    else:
        pt = jnp.full((B, NP), -1, jnp.int32)
        frame_page = jnp.full((F,), -1, jnp.int32)
    return KVPlaneState(
        k_frames=jnp.zeros((KVH, F, P, Dh), cfg.dtype),
        v_frames=jnp.zeros((KVH, F, P, Dh), cfg.dtype),
        page_table=pt,
        k_slab=jnp.zeros((KVH, slab_pages, P, Dh), cfg.dtype),
        v_slab=jnp.zeros((KVH, slab_pages, P, Dh), cfg.dtype),
        kmax=jnp.full((KVH, slab_pages, Dh), -jnp.inf, jnp.float32),
        kmin=jnp.full((KVH, slab_pages, Dh), jnp.inf, jnp.float32),
        cat=jnp.zeros((B, NP, P), bool),
        psf=jnp.ones((B, NP), bool),
        hot_hint=jnp.zeros((B, NP, P), bool),
        page_rows=jnp.zeros((B, NP), jnp.int32),
        frame_page=frame_page,
        clock=jnp.zeros((F,), jnp.int32),
        step=jnp.zeros((), jnp.int32),
    )


# --------------------------------------------------------------------------
# dense paging mode
# --------------------------------------------------------------------------

def append_dense(cfg: KVPlaneConfig, s: KVPlaneState, k_new, v_new, lengths):
    """Write one new token per sequence.  k/v_new: [B, KVH, Dh];
    lengths: [B] current lengths (token goes at index lengths[b])."""
    B, P = cfg.batch, cfg.page_tokens
    page = lengths // P
    slot = lengths % P
    frame = s.page_table[jnp.arange(B), page]            # [B]
    kf = s.k_frames.at[:, frame, slot].set(
        k_new.transpose(1, 0, 2).astype(cfg.dtype))
    vf = s.v_frames.at[:, frame, slot].set(
        v_new.transpose(1, 0, 2).astype(cfg.dtype))
    return s._replace(k_frames=kf, v_frames=vf, step=s.step + 1)


def attend_dense(cfg: KVPlaneConfig, s: KVPlaneState, q, lengths):
    """q: [B, H, Dh] -> [B, H, Dh] via paged attention over the frame pool.
    Also runs the always-on CAT profiling (dense touch -> CAR -> 1)."""
    P, NP = cfg.page_tokens, cfg.num_pages
    page_lens = ops.lengths_to_page_lens(lengths, NP, P)
    out, _used = ops.paged_attention(q, s.k_frames, s.v_frames, s.page_table,
                                     page_lens)
    # profiling: dense attention reads every position below length — the
    # program touched every card (CAR -> 1, pages stay on the paging path)
    pos = (jnp.arange(NP * P)).reshape(NP, P)
    touched = pos[None] < lengths[:, None, None]          # [B, NP, P]
    s = s._replace(cat=jnp.logical_or(s.cat, touched),
                   page_rows=page_lens,
                   clock=jnp.full_like(s.clock, s.step))
    return out, s


# --------------------------------------------------------------------------
# sparse hybrid mode (the Atlas showcase)
# --------------------------------------------------------------------------

def write_page_to_slab(cfg: KVPlaneConfig, s: KVPlaneState, b: int,
                       page_idx, k_page, v_page):
    """Prefill helper: place a full page [KVH, P, Dh] in the far tier and
    update its summaries."""
    gp = b * cfg.num_pages + page_idx
    ks = lax.dynamic_update_index_in_dim(s.k_slab, k_page, gp, axis=1)
    vs = lax.dynamic_update_index_in_dim(s.v_slab, v_page, gp, axis=1)
    kmax = s.kmax.at[:, gp].set(k_page.max(axis=1).astype(jnp.float32))
    kmin = s.kmin.at[:, gp].set(k_page.min(axis=1).astype(jnp.float32))
    return s._replace(k_slab=ks, v_slab=vs, kmax=kmax, kmin=kmin)


class KVFetchPlan(NamedTuple):
    """Fixed-shape ingress plan for one sparse decode step: one entry per
    (sequence, budget slot), N = batch * fetch_budget.  Shapes depend only
    on the config, so a serving host can enqueue the next step's plan while
    the previous step executes (see serving.engine)."""
    seq: jnp.ndarray     # [N] int32 owning sequence
    page: jnp.ndarray    # [N] int32 logical page to fetch (-1 = no-op)
    victim: jnp.ndarray  # [N] int32 destination frame (distinct entries)


def _lookahead_candidates(cfg: KVPlaneConfig, s: KVPlaneState,
                          tops: jnp.ndarray) -> jnp.ndarray:
    """Decode-lookahead section of the fetch plan: ``[B, Qp]`` pages the
    top-page trajectory is trending toward (-1 pad).

    ``prefetch="sequential"`` extrapolates past the newest selected page
    (decode appends march forward).  ``prefetch="majority"`` runs the
    Leap-style vote over the deltas of the (sorted) selected pages — a
    strided retrieval pattern extrapolates along its dominant stride, with
    the most recent delta as the no-majority fallback.  Candidates are
    masked to valid, currently-missing, PSF=paging pages not already in
    the selection (a packed runtime-path page is cheaper to fetch on
    demand than to page in whole speculatively)."""
    B, K = tops.shape
    NP, Qp = cfg.num_pages, cfg.prefetch_budget

    def per_seq(b):
        sel = tops[b]
        valid = sel >= 0
        nv = jnp.sum(valid.astype(jnp.int32))
        srt = jnp.sort(jnp.where(valid, sel, jnp.iinfo(jnp.int32).max))
        if cfg.prefetch == "sequential":
            stride = jnp.asarray(1, jnp.int32)
            have = nv >= 1
        else:  # "majority"
            stride, have = batch_lib.majority_stride(
                srt[1:] - srt[:-1], jnp.maximum(nv - 1, 0))
        base = srt[jnp.clip(nv - 1, 0, K - 1)]
        k = jnp.arange(1, Qp + 1, dtype=jnp.int32)
        cand = jnp.where(have, base + k * stride, -1)
        ok = (cand >= 0) & (cand < NP)
        safe = jnp.clip(cand, 0, NP - 1)
        ok &= s.page_table[b, safe] < 0          # currently missing
        ok &= s.psf[b, safe]                     # PSF mask: paging pages only
        ok &= ~jnp.any(cand[:, None] == sel[None, :], axis=1)
        return jnp.where(ok, cand, -1)

    return jax.vmap(per_seq)(jnp.arange(B))


def plan_fetch(cfg: KVPlaneConfig, s: KVPlaneState, tops: jnp.ndarray
               ) -> KVFetchPlan:
    """Build ONE vectorized fetch plan for the whole ``[B, K]`` top-page
    selection: per-sequence hit/miss classification, first-``fetch_budget``
    miss selection (stable score-rank order), an optional decode-lookahead
    section (``cfg.prefetch``/``cfg.prefetch_budget`` — the same planner
    discipline as ``batch.plan_access``), cross-sequence dedup of the
    flattened global page ids, and eviction victims chosen in a single
    masked top-k over the shared frame pool (wanted-resident frames are
    pinned out of the candidate set; a fetch with no unpinned victim left
    is dropped, lookahead entries first since they rank last)."""
    F, NP = cfg.num_frames, cfg.num_pages
    B, K = tops.shape
    Qp = cfg.prefetch_budget if cfg.prefetch != "none" else 0
    N = cfg.plan_entries
    if N > F:
        raise ValueError(
            f"batch*(fetch_budget+prefetch_budget)={N} fetches per step "
            f"need at least that many frames (have {F})")

    valid = tops >= 0
    safe = jnp.maximum(tops, 0)
    frames_of = s.page_table[jnp.arange(B)[:, None], safe]       # [B, K]
    resident = valid & (frames_of >= 0)
    missing = valid & (frames_of < 0)

    # first `fetch_budget` missing pages per sequence (stable rank order)
    order = jnp.argsort(~missing, axis=1)                        # missing first
    sel = jnp.take_along_axis(tops, order, axis=1)[:, :cfg.fetch_budget]
    selm = jnp.take_along_axis(missing, order, axis=1)[:, :cfg.fetch_budget]
    page = jnp.where(selm, sel, -1).reshape(B * cfg.fetch_budget)
    seq = jnp.repeat(jnp.arange(B, dtype=jnp.int32), cfg.fetch_budget)
    if Qp:
        # ALL demand entries precede ALL lookahead entries in the flat
        # plan, so rank-ordered victim assignment (and the drop-on-
        # pressure tail) favors every sequence's demand over any
        # sequence's speculation
        page = jnp.concatenate(
            [page, _lookahead_candidates(cfg, s, tops).reshape(B * Qp)])
        seq = jnp.concatenate(
            [seq, jnp.repeat(jnp.arange(B, dtype=jnp.int32), Qp)])

    # cross-sequence dedup on the flattened global page ids (defensive: a
    # duplicated selection must not schedule two fetches into two frames)
    gp = seq * NP + page
    i = jnp.arange(N, dtype=jnp.int32)
    ok = page >= 0
    same = (gp[None, :] == gp[:, None]) & ok[None, :]
    first = jnp.min(jnp.where(same, i[None, :], N), axis=1) == i
    page = jnp.where(ok & first, page, -1)

    # fault model (repro.core.faults): a faulted remote fetch drops out of
    # the plan HERE — before victim assignment — so it never claims a frame
    # or evicts anything; attention simply proceeds on what is resident
    # (the sparse path's score masking already tolerates missing pages)
    fc = cfg.faults
    if fc is not None and fc.active:
        okf = page >= 0
        fail = okf & fc.fetch_fail(s.step + 1,
                                   seq * NP + jnp.maximum(page, 0))
        page = jnp.where(fail, -1, page)

    # victims: one masked top-k over the shared pool; every wanted-resident
    # frame is pinned (the soft-pin analogue made hard by the mask).  The
    # coldest victims are compacted onto the VALID fetch entries — a no-op
    # slot (a sequence with fewer misses than budget) must not absorb a
    # cold frame while a real fetch is pushed onto a warm or pinned one.
    # A fetch whose victim would be pinned is dropped instead of executed.
    INF = jnp.iinfo(jnp.int32).max
    pinned = jnp.zeros((F,), bool).at[
        jnp.where(resident, frames_of, F).reshape(-1)].set(True)
    score = jnp.where(pinned, INF, s.clock)
    neg, victims = lax.top_k(-score, N)                          # distinct
    ok = page >= 0
    rank = jnp.cumsum(ok.astype(jnp.int32)) - 1
    usable = ok & ((-neg)[jnp.clip(rank, 0, N - 1)] < INF)
    page = jnp.where(usable, page, -1)
    victim = victims[jnp.where(usable, rank, N - 1)]
    return KVFetchPlan(seq=seq, page=page, victim=victim)


def _evict_math(cfg: KVPlaneConfig, cat_now, old_hint, old_rows):
    """PSF + hot-hint recomputation at page-out (shared by both executors).

    KV pages are append-only and appends write through to the slab, so
    frames are never dirty: page-out is metadata-only.  PSF is recomputed
    from CAR over the FULL page ("would fetching the whole page have been
    worth it?") — a packed runtime page has at most n_hot marked cards, so
    it keeps taking the runtime path.  The hot-set snapshot maps packed
    card bits back through the previous hint (packed slot i == i-th set bit
    of the old hint, by stable sort)."""
    P = cfg.page_tokens
    car = jnp.mean(cat_now.astype(jnp.float32), axis=-1)
    rank = jnp.cumsum(old_hint.astype(jnp.int32), axis=-1) - 1
    packed_back = jnp.logical_and(
        old_hint,
        jnp.take_along_axis(cat_now, jnp.clip(rank, 0, P - 1), axis=-1))
    was_full = old_rows >= P
    hint = jnp.where(was_full[..., None], cat_now, packed_back)
    return car >= cfg.car_threshold, hint


def _ingress_math(cfg: KVPlaneConfig, psf, hot, page_fill):
    """Fetch-path selection (shared by both executors): paging for
    first-touch/append pages and PSF=paging pages, else a runtime packing
    permutation that moves the CAT-marked hot rows to the front (decode
    attention is KV-permutation-invariant)."""
    P = cfg.page_tokens
    n_hot = jnp.sum(hot.astype(jnp.int32), axis=-1)
    take_paging = jnp.logical_or(psf, n_hot == 0)
    perm = jnp.argsort(~hot, axis=-1)                    # stable: hot rows first
    perm = jnp.where(take_paging[..., None],
                     jnp.broadcast_to(jnp.arange(P, dtype=perm.dtype),
                                      perm.shape), perm)
    rows = jnp.where(take_paging, page_fill, n_hot).astype(jnp.int32)
    return perm, rows


def _exec_fetch_batch(cfg: KVPlaneConfig, s: KVPlaneState,
                      plan: KVFetchPlan, fills: jnp.ndarray) -> KVPlaneState:
    """Execute the whole plan with batched data movement: all page-outs as
    one set of masked scatters, all page-ins (whole pages AND packed
    hot-row fetches) as ONE ``kernels.gather_rows`` call per KV tensor.

    Safe to vectorize because the plan's touched page sets are disjoint:
    victims are distinct frames, evicted pages are currently resident,
    fetched pages are currently missing — so every scatter below hits a
    distinct (b, page) slot and all reads can happen against entry state
    (bit-identical to the scalar replay, enforced by the equivalence
    tests)."""
    P, NP, F = cfg.page_tokens, cfg.num_pages, cfg.num_frames
    b, pg, f = plan.seq, plan.page, plan.victim
    N = pg.shape[0]
    ok = pg >= 0
    safe_pg = jnp.maximum(pg, 0)

    # ---- page-out (metadata-only; egress is always page-granular) -------
    old_gp = s.frame_page[f]                             # [N]
    evict = ok & (old_gp >= 0)
    old_safe = jnp.maximum(old_gp, 0)
    old_b, old_pg = old_safe // NP, old_safe % NP
    cat_now = s.cat[old_b, old_pg]                       # [N, P]
    new_psf, hint = _evict_math(cfg, cat_now, s.hot_hint[old_b, old_pg],
                                s.page_rows[old_b, old_pg])
    eidx = jnp.where(evict, old_safe, NP * cfg.batch)   # OOB scatter = drop
    psf = s.psf.reshape(-1).at[eidx].set(new_psf).reshape(s.psf.shape)
    hot_hint = s.hot_hint.reshape(-1, P).at[eidx].set(hint).reshape(
        s.hot_hint.shape)
    cat = s.cat.reshape(-1, P).at[eidx].set(False)
    page_rows = s.page_rows.reshape(-1).at[eidx].set(0)
    page_table = s.page_table.reshape(-1).at[eidx].set(-1)

    # ---- page-in: one batched row gather per KV tensor ------------------
    gp_new = b * NP + safe_pg
    perm, rows_new = _ingress_math(cfg, s.psf[b, safe_pg],
                                   s.hot_hint[b, safe_pg],
                                   fills[b, safe_pg])
    # invalid entries' pages never land (their scatter index is dropped),
    # so the gather can skip the zero-fill pass entirely
    kpages = ops.gather_pages(s.k_slab, gp_new, perm, impl=cfg.kernel_impl,
                              masked=False)
    vpages = ops.gather_pages(s.v_slab, gp_new, perm, impl=cfg.kernel_impl,
                              masked=False)
    fdst = jnp.where(ok, f, F)
    # frame-pool insert: leading-axis scatter on the [KVH*F, P*Dh] page
    # view — one page-sized update window per (head, fetch), O(N) traffic
    # (an axis-1 scatter or a full-pool rebuild both measure slower)
    Dh = cfg.head_dim
    fidx = jnp.where(ok[None, :], jnp.arange(cfg.kv_heads, dtype=jnp.int32
                                             )[:, None] * F + fdst[None],
                     cfg.kv_heads * F).reshape(-1)
    k_frames = s.k_frames.reshape(cfg.kv_heads * F, P * Dh).at[fidx].set(
        kpages.reshape(cfg.kv_heads * N, P * Dh)).reshape(s.k_frames.shape)
    v_frames = s.v_frames.reshape(cfg.kv_heads * F, P * Dh).at[fidx].set(
        vpages.reshape(cfg.kv_heads * N, P * Dh)).reshape(s.v_frames.shape)

    iidx = jnp.where(ok, gp_new, NP * cfg.batch)
    page_table = page_table.at[iidx].set(f).reshape(s.page_table.shape)
    page_rows = page_rows.at[iidx].set(rows_new).reshape(s.page_rows.shape)
    # CAT cleared at page-in ("accessed since last swapped in"); the
    # profiling step marks attended rows afterwards
    cat = cat.at[iidx].set(False).reshape(s.cat.shape)
    frame_page = s.frame_page.at[fdst].set(gp_new)
    clock = s.clock.at[fdst].set(s.step)
    return s._replace(k_frames=k_frames, v_frames=v_frames,
                      page_table=page_table, page_rows=page_rows, cat=cat,
                      psf=psf, hot_hint=hot_hint, frame_page=frame_page,
                      clock=clock)


def _exec_fetch_reference(cfg: KVPlaneConfig, s: KVPlaneState,
                          plan: KVFetchPlan, fills: jnp.ndarray
                          ) -> KVPlaneState:
    """Scalar oracle: replay the identical plan one fetch at a time (the
    seed-era `_evict_and_fetch` body driven by the shared plan)."""
    P, NP = cfg.page_tokens, cfg.num_pages
    N = plan.page.shape[0]

    def fetch_one(j, s):
        b, pg, f = plan.seq[j], plan.page[j], plan.victim[j]

        def do(s):
            old_gp = s.frame_page[f]
            old_b, old_pg = old_gp // NP, old_gp % NP

            def evict(s):
                new_psf, hint = _evict_math(
                    cfg, s.cat[old_b, old_pg][None],
                    s.hot_hint[old_b, old_pg][None],
                    s.page_rows[old_b, old_pg][None])
                return s._replace(
                    psf=s.psf.at[old_b, old_pg].set(new_psf[0]),
                    hot_hint=s.hot_hint.at[old_b, old_pg].set(hint[0]),
                    cat=s.cat.at[old_b, old_pg].set(False),
                    page_rows=s.page_rows.at[old_b, old_pg].set(0),
                    page_table=s.page_table.at[old_b, old_pg].set(-1))

            s = lax.cond(old_gp >= 0, evict, lambda s: s, s)

            gp = b * NP + pg
            kpage = lax.dynamic_index_in_dim(s.k_slab, gp, 1, keepdims=False)
            vpage = lax.dynamic_index_in_dim(s.v_slab, gp, 1, keepdims=False)
            perm, rows = _ingress_math(
                cfg, s.psf[b, pg][None], s.hot_hint[b, pg][None],
                fills[b, pg][None])
            kpage = jnp.take(kpage, perm[0], axis=1)
            vpage = jnp.take(vpage, perm[0], axis=1)
            kf = lax.dynamic_update_index_in_dim(s.k_frames, kpage, f, 1)
            vf = lax.dynamic_update_index_in_dim(s.v_frames, vpage, f, 1)
            return s._replace(
                k_frames=kf, v_frames=vf,
                page_table=s.page_table.at[b, pg].set(f),
                page_rows=s.page_rows.at[b, pg].set(rows[0]),
                frame_page=s.frame_page.at[f].set(gp),
                cat=s.cat.at[b, pg].set(False),
                clock=s.clock.at[f].set(s.step))

        return lax.cond(pg >= 0, do, lambda s: s, s)

    return lax.fori_loop(0, N, fetch_one, s)


def fetch_pages(cfg: KVPlaneConfig, s: KVPlaneState, tops: jnp.ndarray,
                fills: jnp.ndarray, *, mode: str | None = None
                ) -> KVPlaneState:
    """Plan-then-execute ingress for a ``[B, K]`` page selection.

    ``fills`` [B, NP]: appended tokens per page (bounds the valid rows of
    paging fetches).  ``mode`` selects the executor ("batch" | "reference",
    default ``cfg.fetch_mode``); both replay the identical plan."""
    mode = mode or cfg.fetch_mode
    if mode not in ("batch", "reference"):
        raise ValueError(f"unknown fetch mode: {mode!r}")
    plan = plan_fetch(cfg, s, tops)
    if mode == "reference":
        return _exec_fetch_reference(cfg, s, plan, fills)
    return _exec_fetch_batch(cfg, s, plan, fills)


def attend_sparse(cfg: KVPlaneConfig, s: KVPlaneState, q, lengths, *,
                  mode: str | None = None):
    """Hybrid sparse decode.  q: [B, H, Dh] (B = 1 per shard in long_500k).

    Returns (out [B, H, Dh], state)."""
    B, P, NP = cfg.batch, cfg.page_tokens, cfg.num_pages
    K = cfg.sparse_topk
    s = s._replace(step=s.step + 1)

    # 1. offload-space scoring against far-resident summaries
    scores = ops.page_scores(q, s.kmax.reshape(cfg.kv_heads, -1, cfg.head_dim),
                             s.kmin.reshape(cfg.kv_heads, -1, cfg.head_dim))
    # scores: [B, KVH, B*NP] -> per-sequence slice, reduce over kv heads
    per_page = scores.max(axis=1)                        # [B, B*NP]

    def seq_sel(b):
        sl = lax.dynamic_slice_in_dim(per_page[b], b * NP, NP)
        npages = jnp.maximum((lengths[b] + P - 1) // P, 1)
        valid = jnp.arange(NP) < npages
        sl = jnp.where(valid, sl, -jnp.inf)
        _, top = lax.top_k(sl, K)
        top = jnp.where(jnp.arange(K) < jnp.minimum(npages, K), top, -1)
        # always include the newest page (it is being appended)
        newest = npages - 1
        present = jnp.any(top == newest)
        top = top.at[K - 1].set(jnp.where(present, top[K - 1], newest))
        return top

    tops = jax.vmap(seq_sel)(jnp.arange(B))              # [B, K]

    # 2. ensure-local with static fetch budget (ingress via PSF): one
    #    vectorized plan for the whole [B, K] selection, batched execution
    fills = ops.lengths_to_page_lens(lengths, NP, P)      # [B, NP]
    s = fetch_pages(cfg, s, tops, fills, mode=mode)

    # 3. attention over the selected local pages only (columns = selection;
    #    per-column row counts come from page_rows — packed pages included)
    bidx = jnp.arange(B)[:, None]
    sel_frames = s.page_table[bidx, tops]                # [B, K] (-1 if miss)
    sel_valid = sel_frames >= 0
    sel_rows = jnp.where(sel_valid, s.page_rows[bidx, tops], 0)
    out, used = ops.paged_attention(
        q, s.k_frames, s.v_frames,
        jnp.where(sel_valid, sel_frames, -1), sel_rows)

    # 4. always-on profiling: mark the cards of rows whose attention weight
    #    was above the within-page mean (``used`` from the attention kernel)
    #    — flat pages mark everything -> CAR high -> paging; skewed pages
    #    mark the few heavy rows -> CAR low -> runtime
    touched_pages = jnp.where(sel_valid, tops, 0)
    cat = s.cat.at[bidx, touched_pages].set(
        jnp.where(sel_valid[..., None],
                  jnp.logical_or(s.cat[bidx, touched_pages], used),
                  s.cat[bidx, touched_pages]))
    clock = s.clock.at[jnp.maximum(sel_frames, 0).reshape(-1)].set(
        jnp.where(sel_valid.reshape(-1), s.step,
                  s.clock[jnp.maximum(sel_frames, 0).reshape(-1)]))
    return out, s._replace(cat=cat, clock=clock)


# --------------------------------------------------------------------------
# window (ring-buffer) mode: sliding-window attention at long context
# --------------------------------------------------------------------------

def append_window(cfg: KVPlaneConfig, s: KVPlaneState, k_new, v_new, lengths):
    """Ring-buffer append for SWA (mixtral long_500k): the cache covers only
    the window; new tokens overwrite the oldest slot."""
    W = cfg.num_pages * cfg.page_tokens
    return append_dense(cfg, s, k_new, v_new, lengths % W)


def attend_window(cfg: KVPlaneConfig, s: KVPlaneState, q, lengths):
    """Attention over the ring buffer: every resident slot is inside the
    window by construction (older tokens were overwritten)."""
    W = cfg.num_pages * cfg.page_tokens
    return attend_dense(cfg, s, q, jnp.minimum(lengths, W))


# --------------------------------------------------------------------------
# sharded sparse decode: plane shards own disjoint page ranges; partial
# attention per shard, log-sum-exp combine across shards (flash-decoding)
# --------------------------------------------------------------------------

def _attend_pages_partial(q, k_frames, v_frames, table, rows):
    """Unnormalized attention over selected local pages.

    q [B, H, Dh]; k/v_frames [KVH, F, P, Dh]; table/rows [B, K].
    Returns (acc [B, H, Dh] f32, m [B, H, 1], l [B, H, 1],
             used [B, K, P] bool)."""
    B, H, Dh = q.shape
    KVH, F, P, _ = k_frames.shape
    K = table.shape[1]
    G = H // KVH

    def per_seq(qb, pt, pr):
        safe = jnp.maximum(pt, 0)
        k = k_frames[:, safe].reshape(KVH, K * P, Dh)
        v = v_frames[:, safe].reshape(KVH, K * P, Dh)
        qg = qb.reshape(KVH, G, Dh).astype(jnp.float32)
        sc = jnp.einsum("kgd,ksd->kgs", qg, k.astype(jnp.float32))
        sc *= 1.0 / jnp.sqrt(jnp.float32(Dh))
        row = jnp.tile(jnp.arange(P), K)
        valid = (row < jnp.repeat(pr, P)) & jnp.repeat(pt >= 0, P)
        sc = jnp.where(valid[None, None, :], sc, NEG_INF)
        m = sc.max(-1, keepdims=True)                    # [KVH, G, 1]
        p = jnp.exp(sc - m)
        p = jnp.where(valid[None, None, :], p, 0.0)
        l = p.sum(-1, keepdims=True)
        acc = jnp.einsum("kgs,ksd->kgd", p, v.astype(jnp.float32))
        # card signal: weight above within-page mean
        pp = p.reshape(KVH, G, K, P)
        mass = pp.sum(-1, keepdims=True)
        used = (pp * P > mass).any(axis=(0, 1)) & valid.reshape(K, P)
        return (acc.reshape(H, Dh), m.reshape(H, 1), l.reshape(H, 1), used)

    return jax.vmap(per_seq)(q, table, rows)


def attend_sparse_partial(cfg: KVPlaneConfig, s: KVPlaneState, q,
                          first_token, global_len, newest_page, *,
                          mode: str | None = None):
    """One shard's contribution to sharded sparse decode.

    ``first_token``: absolute position of this shard's first page;
    ``global_len``: sequence length; ``newest_page``: local index of the
    append page (-1 if another shard owns it).  Returns (acc, m, l, s)."""
    B, P, NP = cfg.batch, cfg.page_tokens, cfg.num_pages
    K = cfg.sparse_topk
    s = s._replace(step=s.step + 1)
    page_fill = jnp.clip(global_len - first_token - jnp.arange(NP) * P, 0, P
                         ).astype(jnp.int32)
    n_valid_pages = jnp.sum((page_fill > 0).astype(jnp.int32))

    scores = ops.page_scores(q, s.kmax.reshape(cfg.kv_heads, -1, cfg.head_dim),
                             s.kmin.reshape(cfg.kv_heads, -1, cfg.head_dim))
    per_page = scores.max(axis=1)                        # [B, B*NP]

    def seq_sel(b):
        sl = lax.dynamic_slice_in_dim(per_page[b], b * NP, NP)
        valid = jnp.arange(NP) < n_valid_pages
        sl = jnp.where(valid, sl, -jnp.inf)
        _, top = lax.top_k(sl, K)
        top = jnp.where(jnp.arange(K) < jnp.minimum(n_valid_pages, K),
                        top, -1)
        # the append page must stay selected on its owner shard; if the
        # scorer didn't pick it, it replaces the lowest-score selection
        present = jnp.logical_or(jnp.any(top == newest_page),
                                 newest_page < 0)
        top = top.at[K - 1].set(jnp.where(present, top[K - 1], newest_page))
        return top

    tops = jax.vmap(seq_sel)(jnp.arange(B))              # [B, K]

    fills = jnp.broadcast_to(page_fill[None], (B, NP))
    s = fetch_pages(cfg, s, tops, fills, mode=mode)

    bidx = jnp.arange(B)[:, None]
    safe_tops = jnp.maximum(tops, 0)
    sel_frames = jnp.where(tops >= 0, s.page_table[bidx, safe_tops], -1)
    sel_valid = sel_frames >= 0
    sel_rows = jnp.where(sel_valid, s.page_rows[bidx, safe_tops], 0)
    acc, m, l, used = _attend_pages_partial(
        q, s.k_frames, s.v_frames,
        jnp.where(sel_valid, sel_frames, -1), sel_rows)

    touched = jnp.where(sel_valid, tops, 0)
    cat = s.cat.at[bidx, touched].set(
        jnp.where(sel_valid[..., None],
                  jnp.logical_or(s.cat[bidx, touched], used),
                  s.cat[bidx, touched]))
    clock = s.clock.at[jnp.maximum(sel_frames, 0).reshape(-1)].set(
        jnp.where(sel_valid.reshape(-1), s.step,
                  s.clock[jnp.maximum(sel_frames, 0).reshape(-1)]))
    return acc, m, l, s._replace(cat=cat, clock=clock)


def sharded_sparse_decode(cfg: KVPlaneConfig, states, q, lengths, *,
                          mode: str | None = None):
    """Vmapped-over-shards sparse decode with flash-decoding combine.

    ``states``: KVPlaneState with a leading shard axis [D, ...] (sharded
    over the data axis under pjit); q [B, H, Dh] replicated; lengths [B].
    Returns (out [B, H, Dh], states)."""
    D = states.step.shape[0]
    B, P, NP = cfg.batch, cfg.page_tokens, cfg.num_pages
    npages_global = (lengths[0] + P - 1) // P            # B=1 per long run
    shard_ids = jnp.arange(D)
    first_tokens = shard_ids * NP * P
    newest_global = jnp.maximum(npages_global - 1, 0)
    newest_local = jnp.where(newest_global // NP == shard_ids,
                             newest_global % NP, -1).astype(jnp.int32)

    acc, m, l, states = jax.vmap(
        lambda st, ft, nl: attend_sparse_partial(cfg, st, q, ft, lengths[0],
                                                 nl, mode=mode)
    )(states, first_tokens, newest_local)
    # combine: [D, B, H, *]
    m_star = m.max(axis=0, keepdims=True)
    w = jnp.exp(m - m_star)
    l_tot = (l * w).sum(axis=0)
    acc_tot = (acc * w).sum(axis=0)
    out = acc_tot / jnp.maximum(l_tot, 1e-30)
    return out.astype(q.dtype), states


# --------------------------------------------------------------------------
# memoized serve-path jit entry points (state-donating)
# --------------------------------------------------------------------------
# ``jax.jit(partial(attend_sparse, cfg))`` at every call site compiles one
# program per site AND copies the whole state (slabs included) every step —
# the serve loop holds exactly one live state, so the step donates it and
# the far-tier buffers alias through untouched.

@functools.lru_cache(maxsize=None)
def _jitted_attend_sparse(cfg: KVPlaneConfig, mode: str):
    return jax.jit(functools.partial(attend_sparse, cfg, mode=mode),
                   donate_argnums=(0,))


def jitted_attend_sparse(cfg: KVPlaneConfig, mode: str | None = None):
    return _jitted_attend_sparse(cfg, mode or cfg.fetch_mode)


def _sharded_decode_body(cfg: KVPlaneConfig, mode, states, q, lengths):
    """shard_map body of the sharded sparse decode: one shard's partial
    attention + a deterministic all_gather combine.  Identical per-shard
    math to ``sharded_sparse_decode`` (which emulates the gather as a
    stacked-array reduction), so the two are bit-equivalent."""
    s = jax.tree.map(lambda x: x[0], states)
    d = lax.axis_index("far").astype(jnp.int32)
    P, NP = cfg.page_tokens, cfg.num_pages
    npages_global = (lengths[0] + P - 1) // P
    first_token = d * NP * P
    newest_global = jnp.maximum(npages_global - 1, 0)
    newest_local = jnp.where(newest_global // NP == d,
                             newest_global % NP, -1).astype(jnp.int32)
    acc, m, l, s = attend_sparse_partial(cfg, s, q, first_token, lengths[0],
                                         newest_local, mode=mode)
    # deterministic flash-decoding combine: gather the partials in shard
    # order and reduce with the same jnp ops as the vmapped oracle
    accg = lax.all_gather(acc, "far")                    # [S, B, H, Dh]
    mg = lax.all_gather(m, "far")
    lg = lax.all_gather(l, "far")
    m_star = mg.max(axis=0, keepdims=True)
    w = jnp.exp(mg - m_star)
    l_tot = (lg * w).sum(axis=0)
    acc_tot = (accg * w).sum(axis=0)
    out = acc_tot / jnp.maximum(l_tot, 1e-30)
    return out.astype(q.dtype), jax.tree.map(lambda x: x[None], s)


@functools.lru_cache(maxsize=None)
def _jitted_sharded_decode(cfg: KVPlaneConfig, mode: str, mesh):
    if mesh is None:
        return jax.jit(functools.partial(sharded_sparse_decode, cfg,
                                         mode=mode),
                       donate_argnums=(0,))
    from jax.sharding import PartitionSpec as P
    sp = jax.tree.map(lambda _: P("far"),
                      jax.eval_shape(functools.partial(init, cfg)))
    fn = jax.shard_map(functools.partial(_sharded_decode_body, cfg, mode),
                       mesh=mesh, in_specs=(sp, P(), P()),
                       out_specs=(P(), sp), check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


def jitted_sharded_decode(cfg: KVPlaneConfig, mode: str | None = None,
                          mesh=None):
    """Sharded sparse decode entry: ``mesh=None`` is the vmapped
    single-device oracle; a ``far`` mesh (``launch.mesh.make_far_mesh``)
    runs each plane shard on its own device via shard_map, attending its
    slab partition locally and combining with an all_gather — the shared
    sharded pool of the serving deployment."""
    return _jitted_sharded_decode(cfg, mode or cfg.fetch_mode, mesh)


def append_sharded(cfg: KVPlaneConfig, states, k_new, v_new, lengths):
    """Append one token's KV (B=1) into the owning shard's slab page (+ the
    frame copy if resident) and refresh that page's summaries.

    Egress faults (DESIGN.md §6c) gate the whole append atomically: when
    the owning shard's remote write of page ``gpage`` faults at token tick
    ``t``, ownership is masked off and NOTHING mutates — no slab row, no
    kmax/kmin summary, no frame write-through — so the page summaries
    never describe half-appended tokens."""
    D = states.step.shape[0]
    P, NP = cfg.page_tokens, cfg.num_pages
    t = lengths[0]
    gpage = t // P
    slot = t % P
    shard_ids = jnp.arange(D)
    own = gpage // NP == shard_ids
    fc = cfg.faults
    if fc is not None and fc.egress_active:
        own = own & ~fc.egress_fail(t, jnp.broadcast_to(gpage, (D,)),
                                    shard_ids)
    lpage = (gpage % NP).astype(jnp.int32)

    def per_shard(st, is_owner):
        kn = k_new[0].astype(cfg.dtype)                  # [KVH, Dh]
        vn = v_new[0].astype(cfg.dtype)
        gp = 0 * NP + lpage                              # b = 0
        ks = st.k_slab.at[:, gp, slot].set(
            jnp.where(is_owner, kn, st.k_slab[:, gp, slot]))
        vs = st.v_slab.at[:, gp, slot].set(
            jnp.where(is_owner, vn, st.v_slab[:, gp, slot]))
        kmax = st.kmax.at[:, gp].set(
            jnp.where(is_owner,
                      jnp.maximum(st.kmax[:, gp], kn.astype(jnp.float32)),
                      st.kmax[:, gp]))
        kmin = st.kmin.at[:, gp].set(
            jnp.where(is_owner,
                      jnp.minimum(st.kmin[:, gp], kn.astype(jnp.float32)),
                      st.kmin[:, gp]))
        # write-through to the frame copy if the page is resident
        f = st.page_table[0, lpage]
        safe_f = jnp.maximum(f, 0)
        do_frame = jnp.logical_and(is_owner, f >= 0)
        kf = st.k_frames.at[:, safe_f, slot].set(
            jnp.where(do_frame, kn, st.k_frames[:, safe_f, slot]))
        vf = st.v_frames.at[:, safe_f, slot].set(
            jnp.where(do_frame, vn, st.v_frames[:, safe_f, slot]))
        rows = st.page_rows.at[0, lpage].set(
            jnp.where(do_frame, jnp.maximum(st.page_rows[0, lpage], slot + 1),
                      st.page_rows[0, lpage]))
        return st._replace(k_slab=ks, v_slab=vs, kmax=kmax, kmin=kmin,
                           k_frames=kf, v_frames=vf, page_rows=rows)

    return jax.vmap(per_shard)(states, own)
