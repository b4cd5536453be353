"""Serving engine: continuous batching over the Atlas plane.

The engine serves key-value GET/SET requests against a far-memory-resident
object store managed by one of the three data planes (hybrid / paging-only
/ object-only) — the Memcached/WebService analogue used by the latency
benchmarks (paper §5.3).  Requests arrive on a queue with offered-load
pacing; the engine drains them in fixed-size batches (continuous
batching), tracks per-request latency, and periodically runs plane
maintenance (evacuation) exactly like Atlas's concurrent evacuator.

Dispatch is **plan-then-execute, double-buffered** (``dispatch=
"pipelined"``, the default): each batch is submitted as two device calls —
``plan_access`` (vectorized classification/dedup; its output shapes depend
only on the batch size) and ``execute_access`` (the data movement).  The
host never blocks at submit time: it enqueues batch N+1's plan + execute
while batch N is still running on device, and only blocks on the oldest
in-flight result once ``pipeline_depth`` batches are outstanding (or when
a caller explicitly asks for rows).  ``dispatch="sync"`` retires every
batch immediately — the serial engine the pipelined one is benchmarked
against; both produce bit-identical rows and plane state
(tests/test_serving.py).

Latency accounting: a request's latency is charged from its *scheduled
arrival time* (the offered-load pacing clock), not from when the engine
got around to serving it — under saturation the queueing delay is real
latency and is measured as such (the saturation knee of the paper's
latency-throughput curves).

Robust serving (chaos mode): with a :class:`repro.core.faults.Schedule`
on ``EngineConfig.faults`` the plane's remote fetches can fail
deterministically; each plan then carries a per-request ``served`` mask
and the engine closes the loop host-side:

* **retry** — unserved requests re-enter the next tick's batch (bounded
  queue, per-request attempt counts, ``max_retries``);
* **shed** — requests past ``deadline_us`` are dropped at admission and
  counted (``shed_policy="deadline"``), never silently queued;
* **watchdog** — ``_retire_one`` polls with a deadline instead of
  blocking forever, so a wedged device call raises instead of hanging;
* **circuit breaker** — an async health probe (the same ``is_ready()``
  pattern as the epoch watermark) tracks the fetch-failure fraction PER
  SHARD (``[2, shards]`` cumulative counters); a shard whose windowed
  fraction reaches ``breaker_threshold`` trips *alone*
  (``breaker_scope="shard"``, the default — DESIGN.md §6c): its requests
  degrade to paging-local serving (local hits only, no remote fetches, no
  victim writes) while healthy shards stay on the full fast path,
  bit-identically to an all-healthy run.  Every
  ``breaker_probe_every``-th tick dispatches tripped shards normally to
  probe far-tier health, and each shard closes again with hysteresis once
  its own probes come back healthy.  ``breaker_scope="global"`` keeps the
  legacy engine-wide decision (one summed fraction trips every shard at
  once) for comparison.

Host spans: each step of ``submit`` runs inside a
``jax.profiler.TraceAnnotation``, so a profile lays the engine's host
work on the device trace's clock and each idle gap of the device can be
put down to the step the host was in.  ``engine.submit`` (with its
``tick``) holds ``engine.retire`` (and its ``engine.wait`` on the
device), ``engine.upload`` (the ids to the device), ``engine.plan`` and
``engine.execute`` (one device) or ``engine.access`` (the fused sharded
call), ``engine.maintenance`` (with ``engine.evacuate`` and
``engine.epoch`` on the ticks they dispatch) and, on the robust path,
``engine.breaker``.  No span is traced into a program, and with no
profiler running a span costs only its enter and exit.  Every program
the engine dispatches is compiled under its entry point's name
(``jit_plan_access``, ``jit_execute_access``, ...).

``run`` then reports **goodput** (requests actually served) separately
from raw throughput (served + shed) — the split the fault-window
benchmarks plot (benchmarks/fig_faults.py).

Every plane runs on the plan-then-execute batch ingress engine
(``repro.core.batch``); ``EngineConfig.mode="reference"`` swaps in the
scalar oracle executor for debugging and equivalence runs.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function

from repro.core import baselines, plane as plane_lib, shardplane
from repro.core.layout import PlaneConfig
from repro.core import state as state_lib


@dataclasses.dataclass
class EngineConfig:
    plane: str = "hybrid"           # hybrid | paging | object
    batch: int = 64                 # requests per engine tick
    evac_every: int = 64            # hybrid-plane evacuation period (ticks)
    reclaim_free_target: int = 2    # object plane
    mode: str = "batch"             # plan-then-execute engine | "reference" oracle
    dispatch: str = "pipelined"     # "pipelined" double-buffer | "sync"
    pipeline_depth: int = 2         # max in-flight batches before blocking
    # Background evacuation: 0 = one foreground max_pages=16 compaction
    # every evac_every ticks (the pre-slice behavior); >0 = roughly the
    # foreground round's 16-page budget sliced into evac_budget-page
    # plan+execute calls spread evenly across the round's dispatch gaps
    # (ceil(16/budget) slices per round), so no single batch carries a
    # multi-page compaction on its critical path.  Access bits clear once
    # per round, on its first slice — the sliced round's "end of each
    # evacuation".
    evac_budget: int = 0
    # Epoch governor: advance_epoch every this many ticks (hybrid plane;
    # 0 = off).  Dispatched async like everything else.
    epoch_every: int = 0
    # Load-aware epoch scheduling: close an epoch once the plane has moved
    # this many bytes (paging + object traffic) since the last one (0 =
    # off).  A wall-clock tick schedule under-profiles churn bursts and
    # over-profiles idle stretches; the watermark keys the governor to the
    # traffic that actually moves its thresholds.  ``epoch_every`` stays on
    # as the idle-time fallback.  The probe is an async device read polled
    # with ``is_ready()`` so pipelined dispatch never blocks on it.
    epoch_watermark_bytes: int = 0
    # Sharded far tier: partition the plane over this many devices (1 =
    # the single-device plane).  ``batch`` splits evenly across shards
    # (each shard sources batch/shards requests per tick) and access runs
    # the round-based exchange of repro.core.shardplane — on a ``far``
    # mesh when the Engine gets one, else on the vmap oracle.
    shards: int = 1
    # Per-(src, dst) id budget per exchange round (0 = auto: one round,
    # budget = batch/shards, nothing ever spills).
    shard_budget: int = 0
    # Exchange schedule: "overlap" (default) fuses the side channels into
    # one collective per direction and software-pipelines the rounds so
    # collectives overlap the local serves; "serial" is the legacy
    # strictly-ordered 3-hop schedule (bit-identical results — the
    # equivalence suite runs both).
    shard_exchange: str = "overlap"
    # ---- robust / chaos serving ------------------------------------------
    # Deterministic fault schedule (repro.core.faults.Schedule) injected
    # into the plane config: remote fetches fail per the schedule, plans
    # carry a per-request ``served`` mask, and the engine runs the robust
    # submit/retire path below.  None = fault-free (and, with the other
    # knobs at their defaults, the engine is bit-identical to the plain
    # one — enforced by tests/test_faults.py).
    faults: object = None
    # Per-request latency SLO in microseconds (0 = no deadline).  Measured
    # from the scheduled-arrival clock, same as the latency tracker.
    deadline_us: float = 0.0
    # Re-dispatch attempts for requests whose fetch faulted (0 = a faulted
    # request is shed immediately).  Retries ride in the unused tail slots
    # of later ticks' fixed-size batches, so they never grow the compiled
    # shapes.
    max_retries: int = 0
    # "deadline": drop over-deadline requests at admission (counted in
    # shed_requests + deadline_misses).  "none": admit regardless; late
    # service still counts a deadline_miss at retirement.
    shed_policy: str = "deadline"
    # Bounded retry queue: overflow is shed (counted), never buffered
    # unboundedly — a dead far tier must not OOM the host.
    retry_queue_cap: int = 1024
    # _retire_one watchdog: raise TimeoutError if an in-flight batch is
    # still not ready after this many seconds (0 = block forever, the
    # legacy behavior).
    watchdog_s: float = 120.0
    # Circuit breaker: open (degraded paging-local serving) once an async
    # stats probe sees the windowed fetch-failure fraction reach this
    # value (0 = breaker off).  While open, every breaker_probe_every-th
    # tick dispatches normally to probe far-tier health; the breaker
    # closes again once a probe window's failure fraction falls to
    # threshold * hysteresis (recovery needs to look *better* than the
    # trip point — no flapping on the edge).
    breaker_threshold: float = 0.0
    breaker_probe_every: int = 4
    breaker_hysteresis: float = 0.5
    # "shard" (default): each shard trips and recovers on its OWN windowed
    # failure fraction — a single sick shard degrades alone while healthy
    # shards keep the fast path (their ids masked per shard at plan time
    # via the traced degraded mask, DESIGN.md §6c).  "global": the legacy
    # engine-wide decision on the summed fraction (all shards degrade
    # together).  With shards=1 the two are identical.
    breaker_scope: str = "shard"


class LatencyTracker:
    """Latency sink with **bounded memory**.

    The previous tracker appended every sample to a Python list — a
    day-long soak at 1M req/s is ~0.7 GB of floats.  This one keeps an
    exact streaming count and mean plus a fixed-capacity uniform
    reservoir (Vitter's algorithm R, vectorized, deterministically
    seeded) for the percentiles: up to ``capacity`` samples the
    percentiles are exact; beyond that they are an unbiased estimate
    over a uniform sample of the whole stream.
    """

    def __init__(self, capacity: int = 65536, seed: int = 0x5EED):
        self.capacity = int(capacity)
        self._buf = np.empty((self.capacity,), np.float64)
        self._rng = np.random.RandomState(seed)
        self.n = 0
        self._sum = 0.0

    def record(self, t_in: float, t_out: float, n: int):
        if n > 0:
            self.record_us(np.full((int(n),), (t_out - t_in) * 1e6))

    def record_us(self, lat_us):
        """Record a vector of per-request latencies (microseconds)."""
        lat = np.asarray(lat_us, np.float64).reshape(-1)
        if lat.size == 0:
            return
        self._sum += float(lat.sum())
        pos = self.n + np.arange(lat.size)
        head = pos < self.capacity
        if head.any():
            self._buf[pos[head]] = lat[head]
        tail = ~head
        if tail.any():
            # stream element j replaces a random slot with p = capacity/(j+1)
            j = pos[tail]
            r = np.floor(self._rng.random_sample(j.size) * (j + 1)
                         ).astype(np.int64)
            hit = r < self.capacity
            self._buf[r[hit]] = lat[tail][hit]
        self.n += int(lat.size)

    @property
    def lat_us(self) -> list:
        """Retained samples (bounded compat view of the old raw list)."""
        return self._buf[:min(self.n, self.capacity)].tolist()

    def percentile(self, p: float) -> float:
        k = min(self.n, self.capacity)
        return float(np.percentile(self._buf[:k], p)) if k else 0.0

    def summary(self) -> dict:
        if self.n == 0:
            return {}
        a = self._buf[:min(self.n, self.capacity)]
        return {"p50_us": float(np.percentile(a, 50)),
                "p90_us": float(np.percentile(a, 90)),
                "p99_us": float(np.percentile(a, 99)),
                "mean_us": self._sum / self.n, "n": self.n}


class _Inflight(NamedTuple):
    """One dispatched batch awaiting retirement."""
    rows: object            # async device array [batch, D]
    t_sched: float          # batch scheduled-arrival clock (legacy path)
    n: int                  # caller's request count (first n slots)
    served: object = None   # async [batch] bool (robust engines only)
    ids: object = None      # np [batch] int32 slot ids (incl. retries, -1 pad)
    t0s: object = None      # np [batch] float64 per-slot arrival clocks
    att: object = None      # np [batch] int32 per-slot attempt counts


_EMPTY_IDS = np.empty((0,), np.int32)


class Engine:
    """Continuous-batching serving engine (one device).

    ``submit`` enqueues one batch (plan + execute device calls) and returns
    the result as an async array; ``drain`` blocks on everything still in
    flight.  ``serve_batch`` is the synchronous convenience wrapper
    (submit + drain + return rows)."""

    def __init__(self, cfg: EngineConfig, pcfg: PlaneConfig,
                 initial: jnp.ndarray, mesh=None):
        self.cfg = cfg
        if cfg.faults is not None:
            # the schedule rides in the (hashable, static) plane config so
            # every jitted entry point sees the same deterministic streams
            pcfg = dataclasses.replace(pcfg, faults=cfg.faults)
        self.pcfg = pcfg
        self.scfg = None
        sharded = cfg.shards > 1
        epoch_on = (cfg.plane == "hybrid"
                    and (cfg.epoch_every > 0 or cfg.epoch_watermark_bytes > 0))
        self._robust = (cfg.faults is not None or cfg.deadline_us > 0
                        or cfg.max_retries > 0 or cfg.breaker_threshold > 0)
        breaker_on = self._robust and cfg.breaker_threshold > 0
        # memoized jit entry points: engines sharing a PlaneConfig share one
        # compiled executable per op (continuous batching spins up several).
        # The engine holds exactly one live state, so every program that
        # returns it donates it (``donate=True``): XLA writes the new state
        # over the old, with no copy of the slab and no fresh allocation per
        # call.  ``state.stats`` is not donated, so a snapshot of the
        # counters stays readable after later ticks.
        self._plan = self._exec = self._access = None
        self._evac = self._epoch = self._traffic = None
        self._evac_slice = self._evac_slice_clear = None
        self._plan_deg = self._access_degmask = self._health = None
        if sharded:
            assert cfg.batch % cfg.shards == 0, (
                f"batch={cfg.batch} must split evenly over "
                f"{cfg.shards} shards")
            self.scfg = scfg = shardplane.make_config(
                pcfg, cfg.shards, cfg.batch // cfg.shards,
                cfg.shard_budget or None, plane=cfg.plane,
                exchange=cfg.shard_exchange)
            # on a far mesh each shard's state is built on its own device
            self.state = shardplane.jitted_create(scfg, mesh)(initial)
            # fused access: the exchange already interleaves plan+execute
            # per round, so there is no host-visible plan/execute split.
            # Robust engines take the served-channel variant (the verdicts
            # ride the exchange back with the rows).
            self._access = shardplane.jitted_access(
                scfg, cfg.mode, mesh, with_served=self._robust, donate=True)
            if breaker_on:
                # ONE compiled program for every breaker state: the [S]
                # degraded mask arrives as data, so any mix of tripped and
                # healthy shards dispatches without recompiling (all-False
                # reproduces the plain program bit-identically)
                self._access_degmask = shardplane.jitted_access_degmask(
                    scfg, cfg.mode, mesh, with_served=True, donate=True)
            if cfg.plane == "hybrid":
                self._evac = shardplane.jitted_evacuate(scfg, mesh=mesh,
                                                        donate=True)
                if cfg.evac_budget > 0:
                    self._evac_slice = shardplane.jitted_evacuate(
                        scfg, max_pages=cfg.evac_budget, clear_access=False,
                        mesh=mesh, donate=True)
                    self._evac_slice_clear = shardplane.jitted_evacuate(
                        scfg, max_pages=cfg.evac_budget, clear_access=True,
                        mesh=mesh, donate=True)
                if epoch_on:
                    self._epoch = shardplane.jitted_advance_epoch(
                        scfg, mesh, donate=True)
            tcfg = scfg.shard
        elif cfg.plane == "hybrid":
            self.state = state_lib.jitted_create(pcfg)(initial)
            self._plan = plane_lib.jitted_plan_access(pcfg)
            self._exec = plane_lib.jitted_execute_access(pcfg, cfg.mode,
                                                         donate=True)
            if breaker_on:
                self._plan_deg = plane_lib.jitted_plan_access(
                    pcfg, degraded=True)
            self._evac = plane_lib.jitted_evacuate(pcfg, donate=True)
            if cfg.evac_budget > 0:
                # background slices: each is plan_evacuate+execute_evacuate
                # composed into ONE async device call (a two-call split
                # only pays extra dispatch overhead when plan and execute
                # land in the same gap anyway); same 16-page budget per
                # evac_every round as the foreground call
                self._evac_slice = plane_lib.jitted_evacuate(
                    pcfg, max_pages=cfg.evac_budget, clear_access=False,
                    donate=True)
                self._evac_slice_clear = plane_lib.jitted_evacuate(
                    pcfg, max_pages=cfg.evac_budget, clear_access=True,
                    donate=True)
            if epoch_on:
                self._epoch = plane_lib.jitted_advance_epoch(pcfg,
                                                             donate=True)
            tcfg = pcfg
        elif cfg.plane == "paging":
            self.state = state_lib.jitted_create(pcfg)(initial)
            self._plan = baselines.jitted_plan_paging(pcfg)
            self._exec = baselines.jitted_execute_paging(pcfg, cfg.mode,
                                                         donate=True)
            if breaker_on:
                self._plan_deg = baselines.jitted_plan_paging(
                    pcfg, degraded=True)
            tcfg = pcfg
        elif cfg.plane == "object":
            self.state = state_lib.jitted_create(pcfg)(initial)
            self._plan = baselines.jitted_plan_object(pcfg)
            self._exec = baselines.jitted_execute_object(pcfg, cfg.mode,
                                                         donate=True)
            if breaker_on:
                self._plan_deg = baselines.jitted_plan_object(
                    pcfg, degraded=True)
            tcfg = pcfg
        else:
            raise ValueError(cfg.plane)
        if self._evac_slice is not None:
            slices = -(-16 // cfg.evac_budget)          # ceil(16/budget)
            self._evac_slice_period = max(1, cfg.evac_every // slices)
            self._evac_round = 0        # last round whose access-clear ran
        if self._epoch is not None and cfg.epoch_watermark_bytes > 0:
            # bytes moved (paging + object ingress) since the last epoch —
            # the same deltas advance_epoch profiles; sharded states sum
            # elementwise over the stacked [S] counters
            pb, rb = float(tcfg.page_bytes), float(tcfg.row_bytes)

            def epoch_traffic(s):
                return jnp.sum(
                    (s.stats.page_ins - s.epoch_page_ins).astype(jnp.float32)
                    * pb
                    + (s.stats.obj_ins - s.epoch_obj_ins).astype(jnp.float32)
                    * rb)
            self._traffic = jax.jit(epoch_traffic)
        if breaker_on:
            # health probe: cumulative (failed, attempted) remote fetches,
            # kept PER SHARD ([2, shards]; the unsharded plane is one
            # "shard").  Attempts = successful ingress + failures, so
            # degraded ticks (which fetch nothing) contribute ~nothing to
            # either side and a window's fraction measures exactly its
            # *probe* tick's health — a shard's breaker can close off one
            # good probe.  The per-shard columns drive the per-shard trip
            # decision (``breaker_scope="shard"``); ``"global"`` sums them
            # back into the legacy engine-wide signal.

            def fetch_health(s):
                return jnp.stack([
                    jnp.atleast_1d(s.stats.fetch_failures
                                   ).astype(jnp.float32),
                    jnp.atleast_1d(s.stats.page_ins + s.stats.obj_ins
                                   + s.stats.fetch_failures
                                   ).astype(jnp.float32)])
            self._health = jax.jit(fetch_health)
        self._probe = None              # in-flight traffic watermark read
        self._hprobe = None             # in-flight health probe read
        self._hlast = np.zeros((2, cfg.shards), np.float64)
        self.shard_fail_frac = np.zeros((cfg.shards,), np.float64)
        self.breaker_open_shards = np.zeros((cfg.shards,), bool)
        self.served_per_shard = np.zeros((cfg.shards,), np.int64)
        self._retryq: deque = deque()   # (obj_id, t0, attempt)
        self.counters = {"served": 0, "fetch_retries": 0, "shed_requests": 0,
                         "deadline_misses": 0, "degraded_ticks": 0,
                         "breaker_trips": 0, "evac_calls": 0}
        self.latency = LatencyTracker()
        self.ticks = 0
        self._inflight: deque[_Inflight] = deque()      # oldest-first
        # warm the compiled paths so the first request doesn't pay jit time
        if sharded:
            warm = jnp.zeros((cfg.shards, cfg.batch // cfg.shards),
                             jnp.int32)
            if self._robust:
                self.state, _, _ = self._access(self.state, warm)
            else:
                self.state, _ = self._access(self.state, warm)
        else:
            warm = jnp.zeros((cfg.batch,), jnp.int32)
            self.state, _ = self._exec(self.state, warm,
                                       self._plan(self.state, warm))
        if self._evac is not None:
            self.state = self._evac(self.state)
        # the programs below are warmed with their results discarded, so
        # the warm state stays identical to a plain engine's (the
        # fault-free equivalence tests depend on it); a donating one runs
        # on a copy of the state, which it deletes

        def spare():
            return jax.tree.map(jnp.copy, self.state)
        if self._evac_slice is not None:
            # compile-cache the background-slice pair
            jax.block_until_ready(self._evac_slice(spare()))
            jax.block_until_ready(self._evac_slice_clear(spare()))
        if self._epoch is not None:
            jax.block_until_ready(self._epoch(spare()))
        if self._traffic is not None:
            jax.block_until_ready(self._traffic(self.state))
        # warm the degraded/probe entries too — compiling them lazily would
        # land the jit cost inside the fault window and pollute its p99
        if self._plan_deg is not None:
            jax.block_until_ready(self._plan_deg(self.state, warm))
        if self._access_degmask is not None:
            jax.block_until_ready(self._access_degmask(
                spare(), warm, jnp.zeros((cfg.shards,), bool)))
        if self._health is not None:
            jax.block_until_ready(self._health(self.state))
        self.state = self.state._replace(
            stats=jax.tree.map(jnp.zeros_like, self.state.stats),
            epoch_page_ins=jnp.zeros_like(self.state.epoch_page_ins),
            epoch_obj_ins=jnp.zeros_like(self.state.epoch_obj_ins))

    @property
    def breaker_open(self) -> bool:
        """True if ANY shard's breaker is open (back-compat view of the
        per-shard ``breaker_open_shards`` array; with shards=1 it is
        exactly the old engine-global flag)."""
        return bool(self.breaker_open_shards.any())

    # -- pipelined dispatch -------------------------------------------------

    def submit(self, obj_ids: np.ndarray, t_sched: float | None = None):
        """Enqueue one batch; returns its rows as an async device array.

        ``t_sched``: the batch's scheduled arrival time (latency is charged
        from here; defaults to now).  Blocks only when more than
        ``pipeline_depth`` batches are in flight (back-pressure), never on
        the batch being submitted."""
        with TraceAnnotation("engine.submit", tick=self.ticks + 1):
            t_sched = time.time() if t_sched is None else t_sched
            # opportunistic retirement: anything already finished on device
            # is recorded now, so recorded latency tracks actual completion
            # rather than when back-pressure forces a block
            while self._inflight and self._inflight[0].rows.is_ready():
                self._retire_one()
            if self._robust:
                rows = self._submit_robust(obj_ids, t_sched)
            else:
                rows = self._dispatch(obj_ids, t_sched)
            self.ticks += 1
            self._maintenance()
            limit = (0 if self.cfg.dispatch == "sync"
                     else self.cfg.pipeline_depth)
            while len(self._inflight) > limit:
                self._retire_one()
            return rows

    def _dispatch(self, obj_ids, t_sched):
        """Fault-free dispatch (the original engine path)."""
        cfg = self.cfg
        n = len(obj_ids)
        with TraceAnnotation("engine.upload"):
            ids = jnp.asarray(obj_ids, jnp.int32)
            # short batches pad with the plane's negative-id no-ops: fixed
            # shapes keep one compiled program per engine (sharded and
            # unsharded alike)
            if n < cfg.batch:
                ids = jnp.concatenate(
                    [ids, jnp.full((cfg.batch - n,), -1, jnp.int32)])
            if self._access is not None:
                # sharded far tier: the batch splits evenly across source
                # shards
                ids = ids.reshape(cfg.shards, cfg.batch // cfg.shards)
        if self._access is not None:
            with TraceAnnotation("engine.access"):
                self.state, out = self._access(self.state, ids)
                rows_full = out.reshape(cfg.batch, -1)
        else:
            # two async device calls: the plan dispatch is what a sharded
            # deployment runs host-side / on a prefetch stream
            with TraceAnnotation("engine.plan"):
                plan = self._plan(self.state, ids)
            with TraceAnnotation("engine.execute"):
                self.state, rows_full = self._exec(self.state, ids, plan)
        self._inflight.append(_Inflight(rows_full, t_sched, n))
        return rows_full[:n] if n < cfg.batch else rows_full

    def _submit_robust(self, obj_ids, t_sched):
        """Chaos-mode dispatch: deadline shed at admission, retry slots in
        the batch tail, per-slot served verdicts, circuit-breaker routing."""
        cfg = self.cfg
        ids_np = np.asarray(obj_ids, np.int32).reshape(-1)
        n = ids_np.size
        assert n <= cfg.batch, f"batch of {n} > configured batch={cfg.batch}"
        now = time.time()
        shed = (cfg.deadline_us > 0 and cfg.shed_policy == "deadline"
                and n > 0 and (now - t_sched) * 1e6 > cfg.deadline_us)
        if shed:
            # the whole arrival is already past its SLO: count it out
            # instead of queueing work nobody is waiting for
            self.counters["shed_requests"] += n
            self.counters["deadline_misses"] += n
        full = np.full((cfg.batch,), -1, np.int32)
        t0s = np.full((cfg.batch,), now, np.float64)
        att = np.zeros((cfg.batch,), np.int32)
        k = 0
        if n and not shed:
            # new requests first: returned rows[:n] stay aligned with the
            # caller's ids
            full[:n] = ids_np
            t0s[:n] = t_sched
            k = n
        while self._retryq and k < cfg.batch:
            rid, rt0, ratt = self._retryq.popleft()
            if (cfg.deadline_us > 0 and cfg.shed_policy == "deadline"
                    and (now - rt0) * 1e6 > cfg.deadline_us):
                self.counters["shed_requests"] += 1
                self.counters["deadline_misses"] += 1
                continue
            full[k] = rid
            t0s[k] = rt0
            att[k] = ratt
            k += 1
        tick = self.ticks + 1
        sched = cfg.faults
        if sched is not None:
            # host-visible latency spike: the dispatch path stalls (a
            # remote NIC hiccup), deterministically per the schedule
            d_us = sched.spike(tick)
            if d_us > 0.0:
                time.sleep(d_us * 1e-6)
            # slow-but-alive shard windows: the exchange is collective, so
            # the slowest participating shard gates the whole tick.  Pure
            # latency — it never feeds the failure counters, so a slow
            # shard must NOT trip the breaker (slow != dead, §6c).
            slow = sched.slow_us(tick)
            if slow > 0.0:
                time.sleep(slow * 1e-6)
        # per-shard degraded mask for this tick: tripped shards serve
        # paging-local except on probe ticks, healthy shards always run
        # the fast path (with shards=1 this is the old global flag)
        dmask = np.zeros((cfg.shards,), bool)
        if (self._health is not None and self.breaker_open
                and tick % cfg.breaker_probe_every != 0):
            dmask = self.breaker_open_shards.copy()
            self.counters["degraded_ticks"] += int(dmask.sum())
        with TraceAnnotation("engine.upload"):
            ids = jnp.asarray(full)
            if self._access is not None:
                ids = ids.reshape(cfg.shards, cfg.batch // cfg.shards)
            if self._access_degmask is not None:
                deg = jnp.asarray(dmask)
        if self._access is not None:
            with TraceAnnotation("engine.access"):
                if self._access_degmask is not None:
                    self.state, out, sv = self._access_degmask(
                        self.state, ids, deg)
                else:
                    self.state, out, sv = self._access(self.state, ids)
                rows_full = out.reshape(cfg.batch, -1)
                served = sv.reshape(cfg.batch)
        else:
            with TraceAnnotation("engine.plan"):
                plan = (self._plan_deg if dmask[0] else self._plan)(
                    self.state, ids)
            with TraceAnnotation("engine.execute"):
                self.state, rows_full = self._exec(self.state, ids, plan)
            served = plan.served
        self._inflight.append(_Inflight(rows_full, t_sched, n,
                                        served, full, t0s, att))
        if self._health is not None:
            self._breaker_step()
        if shed:
            return jnp.zeros((n, rows_full.shape[1]), rows_full.dtype)
        return rows_full[:n] if n < cfg.batch else rows_full

    @partial(annotate_function, name="engine.maintenance")
    def _maintenance(self):
        """Per-tick background work (evacuation slices, epoch governor)."""
        if self._evac is not None:
            if self.cfg.evac_budget > 0:
                # background evacuation: the foreground round's 16-page
                # budget rides in as evac_budget-page slices spread evenly
                # across the round's dispatch gaps (async device calls —
                # the host moves on to batch N+1 immediately); the
                # access-bit round closes on the evac_every boundary,
                # where the foreground mode used to pay the whole
                # compaction at once
                if self.ticks % self._evac_slice_period == 0:
                    # access bits clear once per evac_every round: on the
                    # first slice of each new round (period need not
                    # divide evac_every)
                    round_id = self.ticks // self.cfg.evac_every
                    with TraceAnnotation("engine.evacuate"):
                        if round_id > self._evac_round:
                            self._evac_round = round_id
                            self.state = self._evac_slice_clear(self.state)
                        else:
                            self.state = self._evac_slice(self.state)
                    self.counters["evac_calls"] += 1
            elif self.ticks % self.cfg.evac_every == 0:
                with TraceAnnotation("engine.evacuate"):
                    self.state = self._evac(self.state)
                self.counters["evac_calls"] += 1
        if self._epoch is not None and self._epoch_due():
            with TraceAnnotation("engine.epoch"):
                self.state = self._epoch(self.state)
            self._probe = None          # watermark restarts from the epoch

    def _epoch_due(self) -> bool:
        """Load-aware epoch schedule: the tick period (``epoch_every``) is
        the fallback; the byte watermark fires as soon as an async traffic
        probe reads past ``epoch_watermark_bytes`` — churn bursts advance
        epochs faster than the wall-clock schedule, idle stretches don't
        churn the governor.  Pipelined dispatch never blocks here: the
        probe is polled with ``is_ready()`` and acted on a tick late."""
        cfg = self.cfg
        if cfg.epoch_every > 0 and self.ticks % cfg.epoch_every == 0:
            return True
        if self._traffic is None:
            return False
        if self._probe is None:
            self._probe = self._traffic(self.state)
            if cfg.dispatch != "sync":
                return False            # poll on a later tick
        if cfg.dispatch == "sync" or self._probe.is_ready():
            due = float(self._probe) >= cfg.epoch_watermark_bytes
            self._probe = None
            return due
        return False

    @partial(annotate_function, name="engine.breaker")
    def _breaker_step(self):
        """Async circuit-breaker update — same non-blocking shape as
        ``_epoch_due``: start a cumulative (failures, attempts) probe,
        poll it with ``is_ready()`` on later ticks, and act on the delta
        since the previous reading.

        ``breaker_scope="shard"`` (default): each shard column trips and
        closes on its OWN windowed failure fraction — a shard only acts
        when its window holds evidence (attempts > 0), opens at
        ``breaker_threshold`` and closes once a window reads back at
        threshold * hysteresis (while open, only probe ticks attempt
        fetches, so the window's fraction is exactly that shard's probes'
        health).  ``"global"``: the legacy decision on the summed
        fractions, all shards together.  ``breaker_trips`` counts
        per-shard openings (engine-wide trips with shards=1)."""
        cfg = self.cfg
        if self._hprobe is None:
            self._hprobe = self._health(self.state)
            if cfg.dispatch != "sync":
                return                  # poll on a later tick
        if cfg.dispatch != "sync" and not self._hprobe.is_ready():
            return
        cur = np.asarray(jax.device_get(self._hprobe),
                         np.float64).reshape(2, -1)
        self._hprobe = None
        d = cur - self._hlast
        self._hlast = cur
        # per-shard window fractions: a single-shard outage lights up one
        # column while the global fraction stays diluted by healthy shards
        self.shard_fail_frac = d[0] / np.maximum(d[1], 1.0)
        thr, hys = cfg.breaker_threshold, cfg.breaker_hysteresis
        if cfg.breaker_scope == "global":
            d_fail, d_att = float(d[0].sum()), float(d[1].sum())
            if d_att <= 0:
                return                  # no fetch attempts -> no evidence
            frac = d_fail / d_att
            if not self.breaker_open and frac >= thr:
                self.breaker_open_shards[:] = True
                self.counters["breaker_trips"] += 1
            elif self.breaker_open and frac <= thr * hys:
                self.breaker_open_shards[:] = False
            return
        # per-shard: evidence, trip and recovery are all column-local
        evidence = d[1] > 0
        frac = d[0] / np.maximum(d[1], 1.0)
        opening = evidence & ~self.breaker_open_shards & (frac >= thr)
        if opening.any():
            self.breaker_open_shards |= opening
            self.counters["breaker_trips"] += int(opening.sum())
        closing = (evidence & self.breaker_open_shards
                   & (frac <= thr * hys))
        self.breaker_open_shards &= ~closing

    @partial(annotate_function, name="engine.wait")
    def _wait_ready(self, rows):
        """Block on a device result, with a watchdog: a wedged device call
        raises ``TimeoutError`` after ``watchdog_s`` instead of hanging
        the serving loop forever."""
        wd = self.cfg.watchdog_s
        if wd <= 0 or rows.is_ready():
            rows.block_until_ready()
            return
        deadline = time.time() + wd
        while not rows.is_ready():
            if time.time() >= deadline:
                raise TimeoutError(
                    f"serving watchdog: in-flight batch still not ready "
                    f"after {wd:.1f}s")
            time.sleep(5e-5)
        rows.block_until_ready()

    @partial(annotate_function, name="engine.retire")
    def _retire_one(self):
        e = self._inflight.popleft()
        # block only on the result actually being returned to a client
        self._wait_ready(e.rows)
        if e.served is None:
            self.latency.record(e.t_sched, time.time(), e.n)
            self.counters["served"] += e.n
            return
        cfg = self.cfg
        sv = np.asarray(jax.device_get(e.served))
        now = time.time()
        real = e.ids >= 0
        ok = real & sv
        if ok.any():
            lat = (now - e.t0s[ok]) * 1e6
            self.latency.record_us(lat)
            self.counters["served"] += int(ok.sum())
            if self.scfg is not None:
                # attribute serves to the owner shard so per-shard
                # breaker benchmarks can read healthy-shard goodput
                owners = e.ids[ok] // self.scfg.shard.num_objs
                np.add.at(self.served_per_shard, owners, 1)
            if cfg.deadline_us > 0:
                self.counters["deadline_misses"] += int(
                    (lat > cfg.deadline_us).sum())
        # unserved slots: bounded retry, else shed (counted) — a request
        # leaves the system exactly once, as served or as shed
        for i in np.nonzero(real & ~sv)[0]:
            if (cfg.max_retries > 0 and e.att[i] < cfg.max_retries
                    and len(self._retryq) < cfg.retry_queue_cap):
                self._retryq.append(
                    (int(e.ids[i]), float(e.t0s[i]), int(e.att[i]) + 1))
                self.counters["fetch_retries"] += 1
            else:
                self.counters["shed_requests"] += 1

    def drain(self):
        """Block on every in-flight batch (end of a workload)."""
        while self._inflight:
            self._retire_one()

    def flush_retries(self):
        """Drive the retry queue to empty with request-less ticks (end of a
        workload): each tick re-dispatches up to ``batch`` queued retries.
        Bounded — anything still unserved when attempts run out is shed."""
        guard = 4 * (self.cfg.max_retries + 2)
        while True:
            self.drain()
            if not self._retryq or guard <= 0:
                break
            self.submit(_EMPTY_IDS)
            guard -= 1
        while self._retryq:             # guard tripped: shed the leftovers
            self._retryq.popleft()
            self.counters["shed_requests"] += 1

    # -- synchronous convenience wrapper ------------------------------------

    def serve_batch(self, obj_ids: np.ndarray) -> jnp.ndarray:
        """Serve one batch synchronously; returns the rows."""
        rows = self.submit(obj_ids)
        self.drain()
        return rows

    def run(self, workload: Iterable[np.ndarray],
            offered_interarrival_s: float = 0.0, on_batch=None) -> dict:
        """Drain a workload; optional pacing simulates offered load.

        With pacing, each batch's latency clock starts at its *scheduled*
        arrival time: serving earlier is impossible, serving later (the
        engine fell behind) counts the queueing delay — reproducing the
        saturation knee of the paper's latency-throughput curves.

        ``on_batch(ids, rows)``, if given, sees every submitted batch and
        its rows (an async device array), e.g. to check them afterwards.

        Reports **goodput** (served requests / wall) next to raw
        throughput ((served + shed) / wall): under faults the two split —
        shed requests leave the system fast but serve nobody."""
        t_run0 = time.time()
        next_arrival = time.time()
        for batch in workload:
            if offered_interarrival_s:
                t_sched = next_arrival
                # retire finished batches while waiting for the next
                # arrival, so recorded latency tracks device completion
                # even when the engine is under-loaded
                while True:
                    now = time.time()
                    if now >= next_arrival:
                        break
                    if self._inflight and self._inflight[0].rows.is_ready():
                        self._retire_one()
                        continue
                    time.sleep(min(2e-4, next_arrival - now))
                next_arrival += offered_interarrival_s
            else:
                t_sched = None
            rows = self.submit(batch, t_sched=t_sched)
            if on_batch is not None:
                on_batch(batch, rows)
        self.drain()
        if self._robust:
            self.flush_retries()
        wall = max(time.time() - t_run0, 1e-9)
        per_shard = None
        if self.scfg is not None:
            raw = shardplane.stats_total(self.state)
            pf = shardplane.paging_fraction(self.scfg, self.state)
            # per-shard failure attribution: the plane already counts
            # fetch_failures on the owner shard that performed the fetch,
            # so a single-shard outage shows up on exactly one entry
            per_shard = [int(x) for x in np.asarray(
                jax.device_get(self.state.stats.fetch_failures))]
        else:
            raw = self.state.stats
            pf = plane_lib.paging_fraction(self.pcfg, self.state)
        stats = {k: int(v) for k, v in
                 jax.device_get(raw)._asdict().items()}
        served = self.counters["served"]
        finished = served + self.counters["shed_requests"]
        report = {"latency": self.latency.summary(), "stats": stats,
                  "paging_fraction": float(pf),
                  "counters": dict(self.counters),
                  "goodput_rps": served / wall,
                  "throughput_rps": finished / wall}
        if per_shard is not None:
            report["fetch_failures_per_shard"] = per_shard
            # egress (writeback) failures land on the shard whose slab the
            # write targeted — the breaker never reads these (fetch-only),
            # so a write-side brownout is visible here even if no trip fires
            report["egress_failures_per_shard"] = [int(x) for x in np.asarray(
                jax.device_get(self.state.stats.egress_failures))]
            report["served_per_shard"] = [int(x)
                                          for x in self.served_per_shard]
        return report
