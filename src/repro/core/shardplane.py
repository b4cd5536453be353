"""Sharded far tier: the hybrid data plane partitioned over a ``far`` axis.

The single-device plane funnels every request batch through ONE slab and
frame pool, so aggregate ingress bandwidth is capped at a single chip.
This module partitions the vpage space across ``shards`` devices: shard
``s`` owns global objects ``[s*O, (s+1)*O)`` (``O`` per-shard), a
contiguous slab partition, its own frame pool, CAT/CAR/EMA profiling state
and governor threshold — a complete per-shard ``PlaneState``, stacked on a
leading shard axis and laid out with ``mesh.far_specs``.

Access is a fixed-shape, round-based exchange (DESIGN.md §Sharded far
tier):

  1. **Pack** (per source shard): dedup the pending ids in
     first-appearance order, bucket them by owner (``owner = id // O`` —
     static, because fill pages are always allocated from the owner's own
     partition, so objects never migrate across shards), and take the
     first ``per_shard_budget`` per destination.  Overflow **spills** to
     the next round (counted in ``stats.ingress_spills``); a duplicate
     multiplicity rides along so the owner can account the collapsed
     requests as hits exactly like the single plane does.
  2. **all_to_all #1**: the ``[S, B]`` id buffers (and counts) transpose
     source-major -> destination-major across the ``far`` axis.
  3. **Serve** (per owner shard): translate to local ids and run today's
     single-device plan-then-execute engine (``batch.access`` and the
     Pallas kernels) against the shard's own partition — padded slots are
     the engine's negative-id no-ops.
  4. **all_to_all #2**: the demand rows return to their requesters, which
     scatter them into request order.

``rounds = ceil(shard_batch / per_shard_budget)`` is static, so every
request is served within one ``access`` call no matter how skewed the
batch; with the default budget (= ``shard_batch``) there is exactly one
round and nothing ever spills.

**Exchange scheduling** (``ShardedPlaneConfig.exchange``): the legacy
``"serial"`` schedule runs pack -> a2a(ids) -> a2a(counts) -> serve ->
a2a(rows) strictly in sequence, three collectives per round.  The default
``"overlap"`` schedule (DESIGN.md §5d) fuses the side channels into one
packed payload per direction (``kernels.ops.fuse_ids_counts`` /
``fuse_rows_flags`` — two collectives per round) and software-pipelines
the rounds: round r+1's pack + ingress collective is issued before round
r's serve retires, and round r's return-row collective overlaps round
r+1's serve (a ``fori`` steady state with a one-round prologue/epilogue
and a depth-2 return buffer whose all\\ -1 dummy round collects as a
bitwise no-op).  Both schedules compute identical values — the pack chain
depends only on the request ids, so reordering its *issue* against the
serves changes nothing — and every buffer keeps its fixed shape, so the
spill protocol and the jit caches are untouched.

The governor aggregates globally: ``advance_epoch`` all-gathers each
shard's epoch byte deltas and hands every shard the same ``(d_page,
d_obj)`` total, so the adaptive thresholds move in lockstep (a
deterministic psum — fixed summation order keeps it bit-reproducible).

**Bit-equivalence discipline** (continuing ``mode="reference"`` from PRs
1-3): every phase above is a plain per-shard function.  The single-device
oracle runs them under ``vmap`` with the collectives emulated as
transposes of the stacked arrays (``mesh=None``); the multi-device path
runs the identical functions inside ``shard_map`` with ``lax.all_to_all``
/ ``lax.all_gather``.  Both execute the same op sequence per shard, so
rows AND full final state match bit-for-bit (tests/test_sharded.py), and
``shards=1`` with the default budget degenerates to the plain plane —
bitwise, stats included.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kernels import ops as kops
from . import baselines
from . import batch as batch_lib
from . import plane as plane_lib
from . import state as st
from .layout import FREE, PlaneConfig


# --------------------------------------------------------------------------
# configuration
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardedPlaneConfig:
    """Static description of a sharded plane (hashable / jit-static).

    ``shard`` is the PER-SHARD plane config (local sizes); the global
    object space is ``shards * shard.num_objs`` ids, owner-major."""

    shard: PlaneConfig
    shards: int                 # S: size of the `far` axis
    shard_batch: int            # R: requests per shard per access call
    per_shard_budget: int       # B: ids exchanged per (src, dst) per round
    plane: str = "hybrid"       # hybrid | paging | object
    exchange: str = "overlap"   # "overlap" pipelined 2-hop | "serial" 3-hop

    def __post_init__(self):
        assert self.shards >= 1
        assert self.shard_batch >= 1
        assert 1 <= self.per_shard_budget <= self.shard_batch
        assert self.plane in ("hybrid", "paging", "object"), self.plane
        assert self.exchange in ("overlap", "serial"), self.exchange

    @property
    def rounds(self) -> int:
        """Static round count: even if every pending id targets one owner,
        ceil(R/B) rounds drain the worst-case per-destination queue."""
        return -(-self.shard_batch // self.per_shard_budget)

    @property
    def num_objs(self) -> int:
        return self.shards * self.shard.num_objs


def shard_config(cfg: PlaneConfig, shards: int) -> PlaneConfig:
    """Slice a GLOBAL plane config into the per-shard config: objects,
    frames and vpages divide evenly across shards (asserted)."""
    for field, n in (("num_objs", cfg.num_objs),
                     ("num_frames", cfg.num_frames),
                     ("num_vpages", cfg.num_vpages)):
        assert n % shards == 0, (
            f"{field}={n} must divide evenly across {shards} shards")
    return dataclasses.replace(cfg, num_objs=cfg.num_objs // shards,
                               num_frames=cfg.num_frames // shards,
                               num_vpages=cfg.num_vpages // shards)


def make_config(cfg: PlaneConfig, shards: int, shard_batch: int,
                per_shard_budget: int | None = None,
                plane: str = "hybrid",
                exchange: str = "overlap") -> ShardedPlaneConfig:
    """Build a sharded config from a GLOBAL plane config.  The default
    budget (= ``shard_batch``) gives one exchange round and no spills."""
    return ShardedPlaneConfig(
        shard=shard_config(cfg, shards), shards=shards,
        shard_batch=shard_batch,
        per_shard_budget=per_shard_budget or shard_batch, plane=plane,
        exchange=exchange)


def create(cfg: ShardedPlaneConfig, initial: jnp.ndarray) -> st.PlaneState:
    """Stacked ``[S, ...]`` plane over the global ``[S*O, D]`` objects."""
    return st.create_sharded(cfg.shard, cfg.shards, initial)


@functools.lru_cache(maxsize=None)
def jitted_create(cfg: ShardedPlaneConfig, mesh=None):
    """``create`` as one compiled program.  On a ``far`` mesh the objects
    arrive split over ``far`` and every output leaf is laid out
    ``P("far")``, so each device builds only its own shard's state."""
    if mesh is None:
        return jax.jit(st.named_partial(create, cfg))
    far = NamedSharding(mesh, P("far"))
    return jax.jit(st.named_partial(create, cfg), in_shardings=far,
                   out_shardings=jax.tree.map(lambda _: far,
                                              _state_specs(cfg)))


# --------------------------------------------------------------------------
# per-shard phases (shared verbatim by the vmap oracle and shard_map)
# --------------------------------------------------------------------------

def _pack_round(cfg: ShardedPlaneConfig, ids, todo):
    """One shard's send buffers for one round.

    ``ids [R]`` global object ids (< 0 = padding); ``todo [R]`` bool marks
    requests not yet served.  Dedup in first-appearance order, bucket by
    owner, keep the first ``B`` per destination; the rest spill.

    Returns ``(send [S, B] ids (-1 pad), cnt [S, B] duplicate multiplicity,
    todo' [R], n_spill [])``."""
    S, B, R = cfg.shards, cfg.per_shard_budget, cfg.shard_batch
    Os = cfg.shard.num_objs
    first = batch_lib._first_of(ids, todo)
    owner = jnp.where(first, ids // Os, S)
    i = jnp.arange(R, dtype=jnp.int32)
    ahead = ((owner[None, :] == owner[:, None]) & first[None, :]
             & (i[None, :] < i[:, None]))
    rank = jnp.sum(ahead.astype(jnp.int32), axis=1)   # per-destination rank
    sent = first & (rank < B)
    dst = jnp.where(sent, owner, S)                   # OOB scatter = drop
    slot = jnp.where(sent, rank, 0)
    send = jnp.full((S, B), -1, jnp.int32).at[dst, slot].set(ids)
    flat = send.reshape(S * B)
    # duplicate multiplicity: how many pending requests each sent id covers
    # (the owner credits cnt-1 extra hits — single-plane dup-hit semantics)
    cnt = jnp.sum((flat[:, None] == ids[None, :]) & todo[None, :], axis=1)
    cnt = jnp.where(flat >= 0, cnt, 0).astype(jnp.int32).reshape(S, B)
    served = jnp.any((ids[:, None] == flat[None, :]) & (flat[None, :] >= 0),
                     axis=1)
    n_spill = jnp.sum((first & ~sent).astype(jnp.int32))
    return send, cnt, todo & ~served, n_spill


def _serve_round(cfg: ShardedPlaneConfig, s, recv, recv_cnt, me, *, mode,
                 degraded: bool = False):
    """Serve one round's received ids against this shard's own plane.
    ``recv/recv_cnt [S, B]`` destination-major buffers; ``me`` the shard
    index.  Returns ``(state, rows [S, B, D], served [S, B])`` (source-
    major again after the reshape — row block ``j`` answers source shard
    ``j``).  ``me`` keys the fault model's per-shard stream, so a
    scheduled outage of shard k fails exactly the fetches k itself would
    have performed."""
    S, B, D = cfg.shards, cfg.per_shard_budget, cfg.shard.obj_dim
    ok = recv >= 0
    lids = jnp.where(ok, recv - me * cfg.shard.num_objs, -1).reshape(S * B)
    if cfg.plane == "hybrid":
        plan = batch_lib.plan_access(cfg.shard, s, lids, shard=me,
                                     degraded=degraded)
        s, rows = batch_lib.execute_access(cfg.shard, s, lids, plan,
                                           mode=mode)
    elif cfg.plane == "paging":
        plan = batch_lib.plan_access(cfg.shard, s, lids, split_by_psf=False,
                                     shard=me, degraded=degraded)
        s, rows = batch_lib.execute_paging_access(cfg.shard, s, lids, plan,
                                                  mode=mode)
    else:
        plan = batch_lib.plan_access(cfg.shard, s, lids, all_runtime=True,
                                     shard=me, degraded=degraded)
        s, rows = batch_lib.execute_object_access(
            cfg.shard, s, lids, plan, mode=mode,
            reclaim=baselines.object_reclaim)
    extra = jnp.sum(jnp.where(ok, recv_cnt - 1, 0)).astype(jnp.int32)
    s = s._replace(stats=st.bump(s.stats, hits=extra))
    return s, rows.reshape(S, B, D), plan.served.reshape(S, B)


def _collect_round(cfg: ShardedPlaneConfig, out, ids, send, got):
    """Scatter one round's returned rows into request order.  ``send [S,B]``
    the ids this shard sent; ``got [S, B, D]`` their rows (back from the
    owners); requests already served in earlier rounds match nothing and
    keep their value."""
    S, B, D = cfg.shards, cfg.per_shard_budget, cfg.shard.obj_dim
    flat = send.reshape(S * B)
    rows = got.reshape(S * B, D)
    match = (ids[:, None] == flat[None, :]) & (flat[None, :] >= 0)
    j = jnp.argmax(match, axis=1)
    hit = jnp.any(match, axis=1)
    return jnp.where(hit[:, None], rows[j], out)


def _collect_served(cfg: ShardedPlaneConfig, out, ids, send, got):
    """Scatter one round's returned served flags into request order (the
    bool analogue of ``_collect_round``; duplicates of a sent id all take
    the owner's verdict)."""
    S, B = cfg.shards, cfg.per_shard_budget
    flat = send.reshape(S * B)
    sv = got.reshape(S * B)
    match = (ids[:, None] == flat[None, :]) & (flat[None, :] >= 0)
    j = jnp.argmax(match, axis=1)
    hit = jnp.any(match, axis=1)
    return jnp.where(hit, sv[j], out)


def _pack_payload(cfg: ShardedPlaneConfig, ids, rows, send):
    """Update payload for one round's send buffer: the LAST-occurrence row
    of each sent id (the single plane's last-write-wins dedup)."""
    S, B, R = cfg.shards, cfg.per_shard_budget, cfg.shard_batch
    flat = send.reshape(S * B)
    i = jnp.arange(R, dtype=jnp.int32)
    match = (flat[:, None] == ids[None, :]) & (flat[:, None] >= 0)
    j = jnp.max(jnp.where(match, i[None, :], -1), axis=1)
    payload = rows[jnp.clip(j, 0, R - 1)]
    payload = jnp.where((j >= 0)[:, None], payload, 0)
    return payload.reshape(S, B, -1).astype(cfg.shard.dtype)


def _serve_update_round(cfg: ShardedPlaneConfig, s, recv, recv_cnt, payload,
                        me, *, mode):
    """Apply one round's received writes to this shard's own plane (the
    same plan-then-execute split as ``_serve_round``, so the pipelined
    schedule interleaves write rounds exactly like read rounds)."""
    S, B, D = cfg.shards, cfg.per_shard_budget, cfg.shard.obj_dim
    ok = recv >= 0
    lids = jnp.where(ok, recv - me * cfg.shard.num_objs, -1).reshape(S * B)
    plan = batch_lib.plan_access(cfg.shard, s, lids, shard=me,
                                 for_update=True)
    s = batch_lib.execute_update(cfg.shard, s, lids,
                                 payload.reshape(S * B, D), plan, mode=mode)
    extra = jnp.sum(jnp.where(ok, recv_cnt - 1, 0)).astype(jnp.int32)
    return s._replace(stats=st.bump(s.stats, hits=extra))


def _epoch_traffic(cfg: PlaneConfig, s) -> jnp.ndarray:
    """One shard's ``[d_page_bytes, d_obj_bytes]`` since its last epoch."""
    d_page = ((s.stats.page_ins - s.epoch_page_ins).astype(jnp.float32)
              * cfg.page_bytes)
    d_obj = ((s.stats.obj_ins - s.epoch_obj_ins).astype(jnp.float32)
             * cfg.row_bytes)
    return jnp.stack([d_page, d_obj])


def _bump_spills(states, spills):
    return states._replace(stats=st.bump(states.stats,
                                         ingress_spills=spills))


# --------------------------------------------------------------------------
# round schedules (written ONCE; the vmap oracle and the shard_map bodies
# inject their own phase closures + collective, so both exchanges execute
# the identical op sequence on both backends)
# --------------------------------------------------------------------------

def _sched_access(cfg: ShardedPlaneConfig, states, ids, *, pack, serve,
                  collect, collect_sv, a2a, with_served):
    """Run every exchange round of one access call.

    ``pack(ids, todo) -> (send, cnt, todo', n_spill)``;
    ``serve(states, recv, recv_cnt) -> (states, rows, served)``;
    ``collect(out, ids, send, rows) -> out``;
    ``collect_sv(out_sv, ids, send, served) -> out_sv``;
    ``a2a`` is the direction transpose (``lax.all_to_all`` inside
    shard_map, a stacked-axis swap on the oracle).  Leading dims come from
    ``ids`` (``[S, R]`` oracle / ``[R]`` per-shard), so the same code
    serves both callers."""
    S, B = cfg.shards, cfg.per_shard_budget
    R, D = cfg.shard_batch, cfg.shard.obj_dim
    lead = ids.shape[:-1]
    todo = ids >= 0
    out = jnp.zeros(lead + (R, D), cfg.shard.dtype)
    out_sv = jnp.zeros(lead + (R,), bool)
    spills = jnp.zeros(lead, jnp.int32)

    if cfg.exchange == "serial":
        # legacy strictly-ordered schedule: three (four with the served
        # channel) collectives per round, each on its own dependence chain
        for _ in range(cfg.rounds):
            send, cnt, todo, nsp = pack(ids, todo)
            spills = spills + nsp
            states, rows, sv = serve(states, a2a(send), a2a(cnt))
            out = collect(out, ids, send, a2a(rows))
            if with_served:
                out_sv = collect_sv(out_sv, ids, send, a2a(sv))
        return _bump_spills(states, spills), out, out_sv

    # -- overlap: fused payloads + software-pipelined rounds ---------------
    def serve_f(states, ing):
        recv, recv_cnt = kops.split_ids_counts(ing)
        states, rows, sv = serve(states, recv, recv_cnt)
        return states, kops.fuse_rows_flags(rows, sv)

    def collect_f(out, out_sv, send, ret):
        rows, sv = kops.split_rows_flags(ret)
        out = collect(out, ids, send, rows)
        if with_served:
            out_sv = collect_sv(out_sv, ids, send, sv)
        return out, out_sv

    # prologue: round 0's ingress is on the wire before any serve runs
    send, cnt, todo, nsp = pack(ids, todo)
    spills = spills + nsp
    ing = a2a(kops.fuse_ids_counts(send, cnt))
    # depth-2 return buffer; the all -1 dummy send matches no request, so
    # the first (dummy) collect is a bitwise no-op
    prev_send = jnp.full(lead + (S, B), -1, jnp.int32)
    prev_ret = jnp.zeros(lead + (S, B, D + 1), cfg.shard.dtype)

    def body(_, c):
        states, todo, out, out_sv, spills, send, ing, p_send, p_ret = c
        # issue round r+1's pack + ingress collective FIRST: it depends
        # only on the request ids, so it overlaps round r's serve below
        n_send, n_cnt, todo, nsp = pack(ids, todo)
        spills = spills + nsp
        n_ing = a2a(kops.fuse_ids_counts(n_send, n_cnt))
        states, ret = serve_f(states, ing)
        # round r's egress overlaps round r+1's serve (collected next trip)
        ret = a2a(ret)
        out, out_sv = collect_f(out, out_sv, p_send, p_ret)
        return (states, todo, out, out_sv, spills, n_send, n_ing, send, ret)

    carry = (states, todo, out, out_sv, spills, send, ing,
             prev_send, prev_ret)
    if cfg.rounds > 1:
        carry = lax.fori_loop(0, cfg.rounds - 1, body, carry)
    states, todo, out, out_sv, spills, send, ing, prev_send, prev_ret = carry
    # epilogue: serve the last round, then drain both outstanding returns
    states, ret = serve_f(states, ing)
    ret = a2a(ret)
    out, out_sv = collect_f(out, out_sv, prev_send, prev_ret)
    out, out_sv = collect_f(out, out_sv, send, ret)
    return _bump_spills(states, spills), out, out_sv


def _sched_update(cfg: ShardedPlaneConfig, states, ids, rows, *, pack,
                  payload_of, serve, a2a):
    """Write-through rounds: same two schedules as ``_sched_access`` minus
    the egress leg (writes return nothing).  Overlap moves two collectives
    per round — the fused ids+counts payload and the row payload (kept
    separate: int32 ids cannot ride bit-safely in a bf16 row buffer)."""
    lead = ids.shape[:-1]
    todo = ids >= 0
    spills = jnp.zeros(lead, jnp.int32)

    if cfg.exchange == "serial":
        for _ in range(cfg.rounds):
            send, cnt, todo, nsp = pack(ids, todo)
            spills = spills + nsp
            payload = payload_of(ids, rows, send)
            states = serve(states, a2a(send), a2a(cnt), a2a(payload))
        return _bump_spills(states, spills)

    def serve_f(states, ing, pay):
        recv, recv_cnt = kops.split_ids_counts(ing)
        return serve(states, recv, recv_cnt, pay)

    send, cnt, todo, nsp = pack(ids, todo)
    spills = spills + nsp
    ing = a2a(kops.fuse_ids_counts(send, cnt))
    pay = a2a(payload_of(ids, rows, send))

    def body(_, c):
        states, todo, spills, ing, pay = c
        n_send, n_cnt, todo, nsp = pack(ids, todo)
        spills = spills + nsp
        n_ing = a2a(kops.fuse_ids_counts(n_send, n_cnt))
        n_pay = a2a(payload_of(ids, rows, n_send))
        states = serve_f(states, ing, pay)
        return (states, todo, spills, n_ing, n_pay)

    carry = (states, todo, spills, ing, pay)
    if cfg.rounds > 1:
        carry = lax.fori_loop(0, cfg.rounds - 1, body, carry)
    states, todo, spills, ing, pay = carry
    states = serve_f(states, ing, pay)
    return _bump_spills(states, spills)


# --------------------------------------------------------------------------
# single-device oracle: vmap over shards, collectives as transposes
# --------------------------------------------------------------------------

def access(cfg: ShardedPlaneConfig, states, ids, *, mode=None,
           degraded=False, with_served: bool = False):
    """Sharded access on ONE device (the bit-equivalence oracle).

    Shape contract: ``states`` is the stacked ``[S, ...]`` plane; ``ids
    [S, R]`` global object ids per source shard (< 0 = padding).  Returns
    ``(states, rows [S, R, D])`` in request order — plus a ``served
    [S, R]`` bool when ``with_served`` (fault-model verdicts riding the
    exchange back to the requesters; padding is never served).

    ``degraded`` is a static bool (all shards degraded, the legacy global
    breaker) or a traced ``[S]`` bool mask — the per-shard breaker
    (DESIGN.md §6c): a masked shard plans no remote I/O and serves local
    hits only, while unmasked shards run the full fast path
    bit-identically to their all-healthy oracle (shard planes are
    independent; only the masked shard's plan changes).  Determinism
    invariant: the vmap oracle and the shard_map path execute the same
    per-shard op sequence and agree bitwise (DESIGN.md §5)."""
    S = cfg.shards
    me = jnp.arange(S, dtype=jnp.int32)
    if isinstance(degraded, bool):
        serve_v = jax.vmap(partial(_serve_round, cfg, mode=mode,
                                   degraded=degraded))
        serve = lambda st_, recv, cnt: serve_v(st_, recv, cnt, me)
    else:
        deg = jnp.asarray(degraded).astype(bool)
        serve_v = jax.vmap(lambda s_, r, c, m, d: _serve_round(
            cfg, s_, r, c, m, mode=mode, degraded=d))
        serve = lambda st_, recv, cnt: serve_v(st_, recv, cnt, me, deg)
    states, out, out_sv = _sched_access(
        cfg, states, ids,
        pack=jax.vmap(partial(_pack_round, cfg)),
        serve=serve,
        collect=jax.vmap(partial(_collect_round, cfg)),
        collect_sv=jax.vmap(partial(_collect_served, cfg)),
        # the emulated all_to_all: [S(src), S(dst), ...] -> [S(dst), S(src), ...]
        a2a=lambda x: jnp.swapaxes(x, 0, 1), with_served=with_served)
    if with_served:
        return states, out, out_sv
    return states, out


def update(cfg: ShardedPlaneConfig, states, ids, rows, *, mode=None):
    """Sharded write-through on ONE device (oracle).  ``rows [S, R, D]``."""
    if cfg.plane != "hybrid":
        raise ValueError("sharded update is a hybrid-plane operation")
    S = cfg.shards
    me = jnp.arange(S, dtype=jnp.int32)
    serve_v = jax.vmap(partial(_serve_update_round, cfg, mode=mode))
    return _sched_update(
        cfg, states, ids, rows,
        pack=jax.vmap(partial(_pack_round, cfg)),
        payload_of=jax.vmap(partial(_pack_payload, cfg)),
        serve=lambda st_, recv, cnt, pay: serve_v(st_, recv, cnt, pay, me),
        a2a=lambda x: jnp.swapaxes(x, 0, 1))


def advance_epoch(cfg: ShardedPlaneConfig, states):
    """Close one epoch on every shard with the GLOBAL traffic aggregate
    (one device; fixed-order sum == the shard_map all_gather combine)."""
    d = jax.vmap(partial(_epoch_traffic, cfg.shard))(states)   # [S, 2]
    tot = jnp.sum(d, axis=0)
    return jax.vmap(lambda s: plane_lib.advance_epoch(
        cfg.shard, s, traffic=(tot[0], tot[1])))(states)


def evacuate(cfg: ShardedPlaneConfig, states, garbage_threshold=None,
             max_pages: int = 16, *, clear_access: bool = True):
    """Per-shard compaction (no cross-shard traffic: objects re-pack onto
    their owner's own fill pages).  Each shard keys the fault model's
    per-shard egress stream with its own index, matching the shard_map
    path's ``lax.axis_index`` bit-for-bit."""
    S = cfg.shards
    me = jnp.arange(S, dtype=jnp.int32)
    return jax.vmap(lambda s_, m: plane_lib.evacuate(
        cfg.shard, s_, garbage_threshold=garbage_threshold,
        max_pages=max_pages, clear_access=clear_access,
        shard=m))(states, me)


# --------------------------------------------------------------------------
# shard_map bodies: identical phases, lax collectives
# --------------------------------------------------------------------------

def _a2a(x):
    return lax.all_to_all(x, "far", split_axis=0, concat_axis=0)


def _access_body(cfg: ShardedPlaneConfig, mode, degraded, with_served,
                 states, ids):
    s = jax.tree.map(lambda x: x[0], states)
    ids = ids[0]
    me = lax.axis_index("far").astype(jnp.int32)
    s, out, out_sv = _sched_access(
        cfg, s, ids,
        pack=partial(_pack_round, cfg),
        serve=lambda st_, recv, cnt: _serve_round(
            cfg, st_, recv, cnt, me, mode=mode, degraded=degraded),
        collect=partial(_collect_round, cfg),
        collect_sv=partial(_collect_served, cfg),
        a2a=_a2a, with_served=with_served)
    s = jax.tree.map(lambda x: x[None], s)
    if with_served:
        return s, out[None], out_sv[None]
    return s, out[None]


def _access_body_degmask(cfg: ShardedPlaneConfig, mode, with_served,
                         states, ids, deg):
    """The per-shard-breaker access body: like ``_access_body`` but the
    degraded flag arrives as data (``deg [S] bool``, one entry per shard)
    instead of baking a static mode into the program — one compiled
    executable serves any mix of tripped and healthy shards."""
    s = jax.tree.map(lambda x: x[0], states)
    ids = ids[0]
    d = deg[0]
    me = lax.axis_index("far").astype(jnp.int32)
    s, out, out_sv = _sched_access(
        cfg, s, ids,
        pack=partial(_pack_round, cfg),
        serve=lambda st_, recv, cnt: _serve_round(
            cfg, st_, recv, cnt, me, mode=mode, degraded=d),
        collect=partial(_collect_round, cfg),
        collect_sv=partial(_collect_served, cfg),
        a2a=_a2a, with_served=with_served)
    s = jax.tree.map(lambda x: x[None], s)
    if with_served:
        return s, out[None], out_sv[None]
    return s, out[None]


def _update_body(cfg: ShardedPlaneConfig, mode, states, ids, rows):
    s = jax.tree.map(lambda x: x[0], states)
    ids, rows = ids[0], rows[0]
    me = lax.axis_index("far").astype(jnp.int32)
    s = _sched_update(
        cfg, s, ids, rows,
        pack=partial(_pack_round, cfg),
        payload_of=partial(_pack_payload, cfg),
        serve=lambda st_, recv, cnt, pay: _serve_update_round(
            cfg, st_, recv, cnt, pay, me, mode=mode),
        a2a=_a2a)
    return jax.tree.map(lambda x: x[None], s)


def _epoch_body(cfg: ShardedPlaneConfig, states):
    s = jax.tree.map(lambda x: x[0], states)
    d = _epoch_traffic(cfg.shard, s)
    # deterministic psum: all_gather + fixed-order sum, bit-identical to
    # the oracle's jnp.sum over the stacked [S, 2] array
    tot = jnp.sum(lax.all_gather(d, "far"), axis=0)
    s = plane_lib.advance_epoch(cfg.shard, s, traffic=(tot[0], tot[1]))
    return jax.tree.map(lambda x: x[None], s)


def _evac_body(cfg: ShardedPlaneConfig, garbage_threshold, max_pages,
               clear_access, states):
    s = jax.tree.map(lambda x: x[0], states)
    me = lax.axis_index("far").astype(jnp.int32)
    s = plane_lib.evacuate(cfg.shard, s, garbage_threshold=garbage_threshold,
                           max_pages=max_pages, clear_access=clear_access,
                           shard=me)
    return jax.tree.map(lambda x: x[None], s)


def _probe_body(cfg: ShardedPlaneConfig, phase, ids):
    """Truncated exchange for phase attribution: ``"pack"`` runs every
    round's pack; ``"ingress"`` additionally moves the fused ingress
    payload.  Returns a per-shard checksum so nothing dead-code
    eliminates."""
    ids = ids[0]
    todo = ids >= 0
    acc = jnp.zeros((), jnp.int32)
    for _ in range(cfg.rounds):
        send, cnt, todo, nsp = _pack_round(cfg, ids, todo)
        x = kops.fuse_ids_counts(send, cnt)
        if phase == "ingress":
            x = _a2a(x)
        acc = acc + jnp.sum(x) + nsp
    return acc[None]


@functools.lru_cache(maxsize=None)
def jitted_phase_probe(cfg: ShardedPlaneConfig, phase: str, mesh):
    """Benchmark-only probe (benchmarks/fig_shard.py): timing ``"pack"``,
    then ``"ingress"`` (pack + fused collective), then a full access gives
    the subtractive pack / collective / serve wall-share breakdown."""
    assert phase in ("pack", "ingress"), phase
    return _jit_on_mesh("phase_probe", partial(_probe_body, cfg, phase),
                        mesh, (P("far"),), P("far"))


# --------------------------------------------------------------------------
# memoized jit entry points (mesh=None -> the single-device oracle)
# --------------------------------------------------------------------------

def _jit_on_mesh(name: str, body, mesh, in_specs, out_specs,
                 donate: bool = False):
    """``body`` run per shard on the ``far`` mesh as one program, compiled
    as ``jit_<name>`` (state-donating with ``donate``, ``st.jit_state``).
    check_vma=False: the plane engine contains fori/while loops, which
    shard_map's varying-axes checker cannot rule on (the state is
    genuinely sharded anyway)."""
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    fn.__name__ = name
    return st.jit_state(fn, donate)


def _state_specs(cfg: ShardedPlaneConfig):
    init = jax.ShapeDtypeStruct((cfg.num_objs, cfg.shard.obj_dim),
                                cfg.shard.dtype)
    tmpl = jax.eval_shape(partial(create, cfg), init)
    return jax.tree.map(lambda _: P("far"), tmpl)


@functools.lru_cache(maxsize=None)
def _jitted_access(cfg: ShardedPlaneConfig, mode, mesh, with_served,
                   degraded, donate=False):
    if mesh is None:
        return st.jit_state(st.named_partial(access, cfg, mode=mode,
                                             degraded=degraded,
                                             with_served=with_served),
                            donate)
    sp = _state_specs(cfg)
    outs = ((sp, P("far"), P("far")) if with_served else (sp, P("far")))
    return _jit_on_mesh(
        "sharded_access",
        partial(_access_body, cfg, mode, degraded, with_served),
        mesh, (sp, P("far")), outs, donate)


def jitted_access(cfg: ShardedPlaneConfig, mode=None, mesh=None, *,
                  with_served: bool = False, degraded: bool = False,
                  donate: bool = False):
    """``(states, ids [S, R]) -> (states, rows [S, R, D])``; ``mesh=None``
    runs the vmap oracle on one device, a ``far`` mesh runs shard_map.
    ``with_served=True`` appends the fault model's per-request ``served
    [S, R]`` verdicts; ``degraded=True`` compiles the hits-only
    circuit-breaker variant; ``donate=True`` the state-donating form
    (``st.DonatingProgram``)."""
    return _jitted_access(cfg, mode or cfg.shard.access_mode, mesh,
                          with_served, degraded, donate)


@functools.lru_cache(maxsize=None)
def _jitted_access_degmask(cfg: ShardedPlaneConfig, mode, mesh, with_served,
                           donate=False):
    if mesh is None:
        def access_degmask(states, ids, deg):
            return access(cfg, states, ids, mode=mode, degraded=deg,
                          with_served=with_served)
        return st.jit_state(access_degmask, donate)
    sp = _state_specs(cfg)
    outs = ((sp, P("far"), P("far")) if with_served else (sp, P("far")))
    return _jit_on_mesh(
        "sharded_access_degmask",
        partial(_access_body_degmask, cfg, mode, with_served),
        mesh, (sp, P("far"), P("far")), outs, donate)


def jitted_access_degmask(cfg: ShardedPlaneConfig, mode=None, mesh=None, *,
                          with_served: bool = True, donate: bool = False):
    """``(states, ids [S, R], deg [S] bool) -> (states, rows, served?)``:
    the per-shard circuit-breaker entry point (DESIGN.md §6c).  Shards
    with ``deg[k]`` set serve local hits only (no remote I/O planned);
    the rest run the full fast path, bit-identically to the plain
    ``jitted_access`` program — passing an all-False mask reproduces it
    exactly, so the engine compiles ONE program for every breaker state."""
    return _jitted_access_degmask(cfg, mode or cfg.shard.access_mode, mesh,
                                  with_served, donate)


@functools.lru_cache(maxsize=None)
def _jitted_update(cfg: ShardedPlaneConfig, mode, mesh):
    if mesh is None:
        return jax.jit(st.named_partial(update, cfg, mode=mode))
    sp = _state_specs(cfg)
    return _jit_on_mesh("sharded_update", partial(_update_body, cfg, mode),
                        mesh, (sp, P("far"), P("far")), sp)


def jitted_update(cfg: ShardedPlaneConfig, mode=None, mesh=None):
    return _jitted_update(cfg, mode or cfg.shard.access_mode, mesh)


@functools.lru_cache(maxsize=None)
def _jitted_advance_epoch(cfg: ShardedPlaneConfig, mesh, donate=False):
    if mesh is None:
        return st.jit_state(st.named_partial(advance_epoch, cfg), donate)
    sp = _state_specs(cfg)
    return _jit_on_mesh("sharded_advance_epoch", partial(_epoch_body, cfg),
                        mesh, (sp,), sp, donate)


def jitted_advance_epoch(cfg: ShardedPlaneConfig, mesh=None, *,
                         donate: bool = False):
    return _jitted_advance_epoch(cfg, mesh, donate)


@functools.lru_cache(maxsize=None)
def _jitted_evacuate(cfg: ShardedPlaneConfig, garbage_threshold, max_pages,
                     clear_access, mesh, donate=False):
    if mesh is None:
        return st.jit_state(st.named_partial(
            evacuate, cfg, garbage_threshold=garbage_threshold,
            max_pages=max_pages, clear_access=clear_access), donate)
    sp = _state_specs(cfg)
    return _jit_on_mesh("sharded_evacuate",
                        partial(_evac_body, cfg, garbage_threshold,
                                max_pages, clear_access),
                        mesh, (sp,), sp, donate)


def jitted_evacuate(cfg: ShardedPlaneConfig, garbage_threshold=None,
                    max_pages: int = 16, clear_access: bool = True,
                    mesh=None, *, donate: bool = False):
    return _jitted_evacuate(cfg, garbage_threshold, max_pages, clear_access,
                            mesh, donate)


# --------------------------------------------------------------------------
# introspection
# --------------------------------------------------------------------------

def stats_total(states) -> st.PlaneStats:
    """Global counters: sum each stat over the shard axis."""
    return st.PlaneStats(jnp.sum(states.stats.counts, axis=0))


def paging_fraction(cfg: ShardedPlaneConfig, states) -> jnp.ndarray:
    """Fraction of allocated pages (across ALL shards) on the paging path."""
    allocated = states.backing != FREE
    pg = jnp.sum((states.psf & allocated).astype(jnp.int32))
    return pg / jnp.maximum(jnp.sum(allocated.astype(jnp.int32)), 1)


def check_invariants(cfg: ShardedPlaneConfig, states) -> dict:
    """Per-shard structural invariants, AND-merged (host-side)."""
    out: dict = {}
    for i in range(cfg.shards):
        for k, v in plane_lib.check_invariants(
                cfg.shard, st.shard_slice(states, i)).items():
            out[k] = out.get(k, True) and v
    return out
