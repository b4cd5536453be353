"""The harness: one cell, one run.

Everything a cell needs is found by name, so later cells, configurations,
traffic mixes and metrics are new files and new entries in
``BENCHMARK.json``, never edits here:

* ``BENCHMARK.json``'s ``configs[].file``  -> the deployment (store sizes,
  engine shapes, YCSB operation and key distribution);
* ``chipbench/traffic/<traffic>.json``     -> the arrival process;
* ``chipbench/metrics/<metric>.py``        -> ``read(rec)``, one number or
  None, for each metric the cell reports;
* ``chipbench/peaks.json``                 -> the chip's peaks by kind.

A run builds the store on the device from ``--seed``, warms it (all
set-up): a load phase that fills the frame pool until pages leave it, then
ticks of the cell's own traffic that hold an evacuation round and two
epochs.  It then offers the cell's load for ``--seconds`` through
``Engine.submit``, timing every request itself, with Python's collector
off so that the loop's own garbage never stalls it.
After the window it reads the device's peak memory, frees the store and
compares the served rows with the reference (``reference.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from collections import deque
from types import SimpleNamespace

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reference  # noqa: E402
import traffic as traffic_lib  # noqa: E402

STATS = ("hits", "misses", "page_ins", "obj_ins", "page_outs",
         "dirty_page_outs", "obj_outs", "evac_moved", "evac_pages", "epochs",
         "ingress_spills", "fetch_failures")
GRACE_S = 60.0          # how long after the close a due request may come
POLL_S = 1e-4           # host poll period while ticks are in flight
LOAD_POOL_FILLS = 1.25  # the load phase reads this many frame pools' worth


def log(t_process: float, msg: str):
    print(f"[{time.perf_counter() - t_process:8.2f}s] {msg}", file=sys.stderr,
          flush=True)


class Refused(RuntimeError):
    """The run cannot be measured here (no chip, unknown device)."""


# ----------------------------------------------------------------------------
# finding things by name
# ----------------------------------------------------------------------------

@dataclasses.dataclass
class Spec:
    root: str                     # checkout root (holds BENCHMARK.json)
    bench: dict

    @classmethod
    def load(cls, root: str) -> "Spec":
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            return cls(root, json.load(f))

    @property
    def home(self) -> str:
        return os.path.join(self.root, self.bench["paths"][0])

    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.home, "traffic", f"{name}.json")) as f:
            return json.load(f)

    def metrics(self, workload: str, trace: bool) -> list:
        """The cell's metric entries: end-to-end ones, or with ``trace``
        the per-layer ones whose ``workloads`` (if any) name the cell."""
        group = "per_layer" if trace else "end_to_end"
        return [m for m in self.bench[group]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str):
        path = os.path.join(self.home, "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def peaks(self, kind: str) -> dict:
        with open(os.path.join(self.home, "peaks.json")) as f:
            table = json.load(f)
        if kind not in table["devices"]:
            raise Refused(f"device kind {kind!r} is not in peaks.json")
        return table["devices"][kind]


# ----------------------------------------------------------------------------
# the store
# ----------------------------------------------------------------------------

def plane_config(cfg: dict):
    """``PlaneConfig`` of the deployment (GLOBAL sizes when sharded)."""
    from repro.core.layout import PlaneConfig
    st = cfg["store"]
    n = int(cfg["recordcount"])
    data_pages = -(-n // st["page_objs"])
    return PlaneConfig(
        num_objs=n, obj_dim=int(cfg["record_f32"]),
        page_objs=int(st["page_objs"]),
        num_frames=max(int(data_pages * st["local_share"]), 8),
        num_vpages=int(st["vpages_per_data_page"]) * data_pages,
        readahead=int(st["readahead"]),
        kernel_impl=st.get("kernel_impl", "auto"))


def engine_config(cfg: dict):
    from repro.serving.engine import EngineConfig
    return EngineConfig(**cfg["engine"])


def build_engine(cfg: dict, seed: int):
    """The engine over a store built on the device from ``seed``."""
    import records
    from repro.serving.engine import Engine
    pcfg, ecfg = plane_config(cfg), engine_config(cfg)
    mesh = sharding = None
    if ecfg.shards > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_far_mesh
        mesh = make_far_mesh(ecfg.shards)
        sharding = NamedSharding(mesh, P("far"))
    data = records.build(seed, pcfg.num_objs, pcfg.obj_dim, sharding)
    eng = Engine(ecfg, pcfg, data, mesh=mesh)
    del data
    jax.block_until_ready(eng.state)
    return eng


def read_stats(eng) -> dict:
    """Plane counters, summed over shards."""
    raw = jax.device_get(eng.state.stats)._asdict()
    return {k: int(np.sum(raw[k])) for k in STATS}


# ----------------------------------------------------------------------------
# load: ticks of whole requests through Engine.submit
# ----------------------------------------------------------------------------

class Loader:
    """Packs whole requests into fixed-size ticks, keeps at most
    ``pipeline_depth`` ticks in flight, and sees each finish."""

    def __init__(self, eng, keep: bool):
        self.eng = eng
        self.batch = eng.cfg.batch
        self.depth = eng.cfg.pipeline_depth
        self.inflight: deque = deque()
        self.keep = keep            # copy every served tick for the check
        self.kept: list = []        # (ids [batch], rows [batch, D], members)
        self.ids_real = 0
        self.ticks = 0

    def can_submit(self) -> bool:
        return len(self.inflight) < self.depth

    def take(self, pending: deque, reqs) -> tuple:
        """Whole requests from the front of ``pending`` that fit a tick."""
        ids = np.full((self.batch,), -1, np.int32)
        members, off = [], 0
        while pending and off + len(reqs[pending[0]]) <= self.batch:
            r = pending.popleft()
            ln = len(reqs[r])
            ids[off:off + ln] = reqs[r]
            members.append((r, off, ln))
            off += ln
        return ids, members, off

    def submit(self, ids, members, n_ids):
        with jax.profiler.TraceAnnotation("cb.submit"):
            rows = self.eng.submit(ids)
        self.inflight.append((rows, ids, members))
        self.ids_real += n_ids
        self.ticks += 1

    def retire_ready(self) -> list:
        """Members of every tick that has finished, oldest first."""
        done = []
        while self.inflight and self.inflight[0][0].is_ready():
            with jax.profiler.TraceAnnotation("cb.retire"):
                rows, ids, members = self.inflight.popleft()
                if self.keep:
                    self.kept.append((ids, np.asarray(rows), members))
                done += members
        return done


@contextlib.contextmanager
def collector_off():
    """Python's garbage collector off for a measured window, so that the
    load loop's own garbage never stalls it."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        gc.unfreeze()


def _sleep(dt):
    with jax.profiler.TraceAnnotation("cb.wait"):
        time.sleep(dt)


def load_order(n: int, shards: int, batch: int) -> np.ndarray:
    """Record ids in YCSB's load order: ascending within each shard's
    contiguous range, the shards interleaved ``batch // shards`` ids at a
    time, so every tick loads each shard alike."""
    r = batch // shards
    ids = np.arange(n, dtype=np.int32).reshape(shards, -1)
    ids = ids[:, :ids.shape[1] // r * r].reshape(shards, -1, r)
    return ids.transpose(1, 0, 2).reshape(-1)


def load_ticks(eng) -> int:
    """Ticks of the load phase: record ids in load order until
    ``LOAD_POOL_FILLS`` times each shard's frame pool has been read in, so
    the pool is full and pages have begun to leave it."""
    per_shard = eng.cfg.batch // eng.cfg.shards
    frames = eng.pcfg.num_frames // eng.cfg.shards
    want = LOAD_POOL_FILLS * frames * eng.pcfg.page_objs
    return int(np.ceil(want / per_shard))


def warm_up(eng, cfg: dict, seed: int, warm: dict) -> dict:
    """The store's warm state, a fixed amount of work from the seed (so
    set-up is the same from run to run), through ``Engine.submit`` at the
    cell's own batch shape:

    1. YCSB's load phase, read back: :func:`load_ticks` ticks of record
       ids in load order, which fill the frame pool and start evictions;
    2. ``warm['ticks']`` ticks of the cell's own traffic (stream 1 of the
       seed).

    It must leave page-outs on every shard, ``warm['evac_rounds']``
    evacuation rounds and ``warm['epochs']`` closed epochs.  Returns the
    plane counters after."""
    order = load_order(eng.pcfg.num_objs, eng.cfg.shards, eng.cfg.batch)
    n_load = load_ticks(eng)
    if n_load * eng.cfg.batch > len(order):
        raise RuntimeError(f"the load phase needs {n_load} ticks, more "
                           f"than the {len(order) // eng.cfg.batch} the "
                           f"records fill")
    for k in range(n_load):
        eng.submit(order[k * eng.cfg.batch:(k + 1) * eng.cfg.batch])
    src = traffic_lib.Requests(cfg, seed, 1)
    reqs: list = []
    pending: deque = deque()
    drv = Loader(eng, keep=False)
    for _ in range(int(warm["ticks"])):
        if len(pending) < eng.cfg.batch:
            base = len(reqs)
            reqs += src.next(4 * eng.cfg.batch)
            pending.extend(range(base, len(reqs)))
        ids, _, _ = drv.take(pending, reqs)
        eng.submit(ids)
    eng.drain()
    raw = jax.device_get(eng.state.stats)
    epochs = int(np.max(raw.epochs))
    page_outs = int(np.min(raw.page_outs))
    if (eng.counters["evac_calls"] < warm["evac_rounds"]
            or epochs < warm["epochs"]
            or page_outs < 1):
        raise RuntimeError(
            f"the warm-up ({n_load} load ticks, {warm['ticks']} of traffic) "
            f"ran {eng.counters['evac_calls']} evacuations, {epochs} epochs "
            f"and {page_outs} page-outs on its emptiest shard; the "
            f"configuration asks for {warm['evac_rounds']}, "
            f"{warm['epochs']} and at least 1")
    return read_stats(eng)


class Window:
    """Per-request clocks of one measured window."""

    def __init__(self):
        self.t_start: list = []
        self.t_done: list = []
        self.late: list = []        # open loop: host lateness per arrival

    def add(self, t: float) -> int:
        self.t_start.append(t)
        self.t_done.append(np.nan)
        return len(self.t_start) - 1


def drive_open(drv: Loader, reqs_src, rate: float, seconds: float, seed: int,
               on_tick=None):
    """Open-loop Poisson arrivals; returns the window and its close time."""
    arr = traffic_lib.open_arrivals(seed, rate, seconds)
    reqs = reqs_src.next(len(arr))
    w = Window()
    pending: deque = deque()
    t0 = time.perf_counter()
    due = t0 + arr
    for t in due:
        w.add(float(t))
    nxt, n = 0, len(arr)
    while True:
        now = time.perf_counter()
        while nxt < n and due[nxt] <= now:
            w.late.append(now - due[nxt])
            pending.append(nxt)
            nxt += 1
        for r, _, _ in drv.retire_ready():
            w.t_done[r] = now
        if on_tick is not None:
            on_tick(now - t0, drv.ticks)
        if pending and drv.can_submit():
            with jax.profiler.TraceAnnotation("cb.generate"):
                ids, members, k = drv.take(pending, reqs)
            drv.submit(ids, members, k)
            continue
        if nxt == n and not pending and not drv.inflight:
            break
        if now > t0 + seconds + GRACE_S:
            break
        wait = POLL_S if drv.inflight else \
            min(1e-3, max(0.0, (due[nxt] if nxt < n else now) - now))
        _sleep(wait)
    return w, t0, reqs


def drive_closed(drv: Loader, reqs_src, clients: int, seconds: float,
                 on_tick=None):
    """Closed loop: ``clients`` each keep one request outstanding and send
    the next as soon as the last one's rows are ready."""
    w = Window()
    reqs: list = []
    pending: deque = deque()

    def send(t):
        if len(reqs) <= len(w.t_start):
            reqs.extend(reqs_src.next(4096))
        pending.append(w.add(t))

    t0 = time.perf_counter()
    for _ in range(clients):
        send(t0)
    while True:
        now = time.perf_counter()
        for r, _, _ in drv.retire_ready():
            w.t_done[r] = now
            if now < t0 + seconds:
                send(now)
        if on_tick is not None:
            on_tick(now - t0, drv.ticks)
        if pending and drv.can_submit():
            with jax.profiler.TraceAnnotation("cb.generate"):
                ids, members, k = drv.take(pending, reqs)
            drv.submit(ids, members, k)
            continue
        if not pending and not drv.inflight:
            break
        if now > t0 + seconds + GRACE_S:
            break
        _sleep(POLL_S)
    return w, t0, reqs


# ----------------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------------

def shrink(cfg: dict, rehearse: dict) -> dict:
    """The CPU rehearsal's tiny copy of a deployment (self-checks only)."""
    cfg = json.loads(json.dumps(cfg))
    cfg["recordcount"] = rehearse["records_per_shard"] * \
        cfg["engine"].get("shards", 1)
    cfg["engine"]["batch"] = min(cfg["engine"]["batch"], rehearse["batch"])
    cfg["store"]["kernel_impl"] = rehearse.get("kernel_impl", "interpret")
    return cfg


def devices_for(chips: int, rehearse):
    devs = jax.devices()
    if rehearse is None:
        if devs[0].platform != "tpu":
            raise Refused(f"JAX found no TPU (platform={devs[0].platform})")
        if len(devs) < chips:
            raise Refused(f"the cell needs {chips} chips, JAX found "
                          f"{len(devs)}")
    return devs[:chips]


def run(spec: Spec, workload: str, seed: int, seconds: float, trace: bool,
        t_process: float, control: str | None = None,
        rehearse: dict | None = None, dump: str | None = None) -> dict:
    """One run of one cell; returns the result object (the last line)."""
    cell = spec.workload(workload)
    devs = devices_for(cell["chips"], rehearse)
    kind = devs[0].device_kind
    peaks = spec.peaks(kind) if rehearse is None else {}
    cfg = spec.config(cell["config"])
    if rehearse is not None:
        cfg = shrink(cfg, rehearse)
    tcfg = spec.traffic(cell["traffic"])
    if cfg["engine"].get("shards", 1) != cell["chips"] and rehearse is None:
        raise Refused("the configuration's shards differ from the cell's "
                      "chips")

    log(t_process, "store: building")
    eng = build_engine(cfg, seed)
    log(t_process, "store: built; warming up")
    s0 = warm_up(eng, cfg, seed, cfg["warmup"])
    n_load = load_ticks(eng)
    log(t_process, f"warm: {s0}")
    ticks0 = eng.ticks
    drv = Loader(eng, keep=True)
    src = traffic_lib.Requests(cfg, seed, 2)

    stretch = None
    if trace:
        import devtrace
        stretch = devtrace.Stretch(seconds, eng.cfg.evac_every,
                                   lambda: eng.state.stats)

    compiles = _CompileCounter()
    setup_s = time.perf_counter() - t_process
    with collector_off():
        if tcfg["arrival"] == "open":
            w, t0, reqs = drive_open(drv, src, float(tcfg["rate_per_s"]),
                                     seconds, seed, on_tick=stretch)
        elif tcfg["arrival"] == "closed":
            w, t0, reqs = drive_closed(
                drv, src, traffic_lib.closed_clients(tcfg, eng.cfg.batch),
                seconds, on_tick=stretch)
        else:
            raise ValueError(tcfg["arrival"])
    if stretch is not None:
        stretch.close()
    log(t_process, "window closed")
    eng.drain()
    n_compiles = compiles.stop()
    s1 = read_stats(eng)
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               if d.memory_stats() else 0 for d in devs)
    window_ticks = eng.ticks - ticks0
    geometry = (eng.pcfg.row_bytes, eng.pcfg.page_bytes)
    impl = _kernel_impl(eng)
    del eng, drv.eng
    gc.collect()

    log(t_process, "store freed; checking")
    # ---- the check: after the window, with the store freed ---------------
    t_done = np.asarray(w.t_done, np.float64)
    t_start = np.asarray(w.t_start, np.float64)
    bad_req = set()
    bad_rows, checked_rows = 0, 0
    for ids, rows, members in drv.kept:
        if control == "bf16":
            rows = reference.to_bfloat16(reference.rows(seed, ids.clip(0),
                                                        rows.shape[1]))
        real = ids >= 0
        diff = reference.mismatches(seed, ids[real], rows[real])
        bad_rows += int(diff.sum())
        checked_rows += int(real.sum())
        if diff.any():
            where = np.nonzero(real)[0][diff]
            for r, off, ln in members:
                if np.any((where >= off) & (where < off + ln)):
                    bad_req.add(r)
    lost = int(np.isnan(t_done).sum())
    delta = {k: s1[k] - s0[k] for k in STATS}
    unaccounted = abs(delta["hits"] + delta["misses"] - drv.ids_real)
    checks = {"mismatched_rows": [bad_rows, 0],
              "unaccounted_ids": [unaccounted, 0],
              "lost_requests": [lost, 0]}
    correct = all(v <= lim for v, lim in checks.values())
    attempted = len(t_start)
    failed = lost + len(bad_req)

    ok_done = ~np.isnan(t_done)
    if bad_req:
        ok_done[list(bad_req)] = False
    rec = SimpleNamespace(
        cell=cell, config=cfg, traffic=tcfg, peaks=peaks, chips=len(devs),
        window_s=float(seconds), setup_s=setup_s,
        lat_ms=(t_done[~np.isnan(t_done)] - t_start[~np.isnan(t_done)]) * 1e3,
        good_in_window=int(np.sum(ok_done & (t_done <= t0 + seconds))),
        requests=attempted, ticks=window_ticks, ids=drv.ids_real,
        stats=delta, row_bytes=geometry[0], page_bytes=geometry[1],
        trace=None, stretch=None)
    if trace:
        rec.trace = stretch.read()
        rec.stretch = stretch.counters()
        if dump:
            os.makedirs(dump, exist_ok=True)
            rec.trace.save(os.path.join(dump, f"{workload}.{seed}.trace.json.gz"))
            with open(os.path.join(dump, f"{workload}.{seed}.summary.json"),
                      "w") as f:
                json.dump(rec.trace.summary(), f, indent=1)

    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed)}
    late = np.asarray(w.late) * 1e3
    info = {"setup_s": setup_s, "load_ticks": n_load, "warm": s0,
            "window_ticks": window_ticks, "requests": attempted,
            "ids": drv.ids_real, "checked_rows": checked_rows,
            "compiles_in_window": n_compiles, "kernel_impl": impl,
            "late_ms_p99": float(np.percentile(late, 99)) if late.size else 0.0,
            "stats": delta}
    if rehearse is not None:
        # CPU rehearsal: counts only, never a device metric's name
        out.update(device=device, rehearsal=info)
    else:
        metrics = {}
        for m in spec.metrics(workload, trace):
            v = spec.reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["metrics"] = metrics
        if trace:
            device["busy_s"] = rec.trace.mean_busy_s()
            device["window_s"] = rec.trace.window_s
            out["breakdown"] = {"device_ops": rec.trace.top_ops(10),
                                "idle_gaps": rec.trace.idle_gaps(10)}
        out["device"] = device
        out["info"] = info
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def _kernel_impl(eng) -> str:
    from repro.kernels import ops
    return ops.resolve_impl(eng.pcfg.kernel_impl)


class _CompileCounter:
    """Counts backend compilations while it is open (there should be none
    inside the measured window)."""

    def __init__(self):
        self.n = 0
        self._on = True

        def listener(event, duration, **_):
            if self._on and "backend_compile" in event:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listener)

    def stop(self) -> int:
        self._on = False
        return self.n
