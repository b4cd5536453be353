"""The engine's host spans and the names of the programs it dispatches.

``Engine.submit`` wraps each of its steps in a ``jax.profiler``
``TraceAnnotation`` (``engine.*``), so a profile puts the device's idle
time down to the engine's own steps; every program it dispatches compiles
under its entry point's name (``jit_<entry point>``), so a profile shows
each program by name.  Both are read here from a CPU profile and from the
lowered modules.
"""
import functools
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import baselines, shardplane, state as state_lib
from repro.core.layout import PlaneConfig
from repro.launch.mesh import make_far_mesh
from repro.serving.engine import Engine, EngineConfig

N_OBJS, DIM, BATCH, TICKS = 256, 8, 16, 8
EVAC_EVERY, EPOCH_EVERY = 4, 2


def _pcfg(**kw):
    return PlaneConfig(num_objs=N_OBJS, obj_dim=DIM, page_objs=8,
                       num_frames=12, num_vpages=3 * (N_OBJS // 8), **kw)


def _engine(plane="hybrid", **kw):
    data = jnp.arange(N_OBJS * DIM, dtype=jnp.float32).reshape(N_OBJS, DIM)
    return Engine(EngineConfig(plane=plane, batch=BATCH, **kw), _pcfg(),
                  data)


# the three dispatch paths of submit: one device, the fused sharded access
# (here on the single-device oracle, paging plane: it has no maintenance,
# and compiles in a fraction of the hybrid plane's time) and the robust
# (breaker) path, whose byte watermark never fires in these few ticks
KINDS = {
    "plain": dict(),
    "sharded": dict(plane="paging", shards=2),
    "robust": dict(breaker_threshold=0.5, epoch_watermark_bytes=1 << 30),
}


@pytest.fixture(scope="module")
def engines():
    """One engine of each kind, built when first asked for."""
    @functools.lru_cache(maxsize=None)
    def get(kind):
        return _engine(evac_every=EVAC_EVERY, epoch_every=EPOCH_EVERY,
                       **KINDS[kind])
    return get


def _engine_spans(logdir):
    """``[name, start_ns, end_ns, tick stat or None]`` of every
    ``engine.*`` host event in the profile under ``logdir``."""
    found = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    assert len(found) == 1, found
    out = []
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append([e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                dict(e.stats).get("tick")])
    return out


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_submit_spans_each_step_of_each_tick(kind, engines, tmp_path):
    eng = engines(kind)
    assert eng.ticks == 0
    rng = np.random.RandomState(0)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(TICKS):
            eng.submit(rng.randint(0, N_OBJS, BATCH).astype(np.int32))
    eng.drain()
    spans = _engine_spans(tmp_path)

    submits = sorted((s for s in spans if s[0] == "engine.submit"),
                     key=lambda s: s[1])
    assert [s[3] for s in submits] == list(range(1, TICKS + 1))

    def tick_of(span):
        owner = [t for t in submits if t[1] <= span[1] and span[2] <= t[2]]
        assert len(owner) == 1, f"{span[0]} lies in no one engine.submit"
        return owner[0][3]

    per_tick: dict = {}
    for s in spans:
        if s[0] != "engine.submit":
            per_tick.setdefault(s[0], []).append(tick_of(s))
    every = list(range(1, TICKS + 1))
    dispatch = (["engine.access"] if kind == "sharded"
                else ["engine.plan", "engine.execute"])
    for name in ["engine.upload", "engine.maintenance"] + dispatch:
        assert sorted(per_tick.pop(name)) == every, name
    if eng.cfg.plane == "hybrid":
        assert sorted(per_tick.pop("engine.evacuate")) == \
            list(range(EVAC_EVERY, TICKS + 1, EVAC_EVERY))
        assert sorted(per_tick.pop("engine.epoch")) == \
            list(range(EPOCH_EVERY, TICKS + 1, EPOCH_EVERY))
    if kind == "robust":
        assert sorted(per_tick.pop("engine.breaker")) == every
    # pipelined, two ticks in flight: back-pressure retires from the
    # third tick on, each retirement waiting on the device once
    retired = per_tick.pop("engine.retire")
    assert sorted(per_tick.pop("engine.wait")) == sorted(retired)
    assert TICKS - eng.cfg.pipeline_depth <= len(retired) <= TICKS
    assert not per_tick, f"unexpected spans {sorted(per_tick)}"
    for w in (s for s in spans if s[0] == "engine.wait"):
        assert any(r[1] <= w[1] and w[2] <= r[2] for r in spans
                   if r[0] == "engine.retire")
    for s in spans:
        if s[0] in ("engine.evacuate", "engine.epoch"):
            assert any(m[1] <= s[1] and s[2] <= m[2] for m in spans
                       if m[0] == "engine.maintenance"), s[0]


def _module(jitted, *args) -> str:
    return re.search(r"module @(\w+)",
                     jitted.lower(*args).as_text()).group(1)


def test_engine_programs_compile_under_their_names(engines):
    ids = jnp.zeros((BATCH,), jnp.int32)
    seen = []

    def check(jitted, want, *args):
        got = _module(jitted, *args)
        seen.append(got)
        assert got == f"jit_{want}", (want, got)

    eng = engines("robust")
    s, pcfg = eng.state, eng.pcfg
    plan = jax.eval_shape(eng._plan, s, ids)
    check(eng._plan, "plan_access", s, ids)
    check(eng._plan_deg, "plan_access", s, ids)
    check(eng._exec, "execute_access", s, ids, plan)
    check(eng._evac, "evacuate", s)
    check(eng._epoch, "advance_epoch", s)
    check(eng._traffic, "epoch_traffic", s)
    check(eng._health, "fetch_health", s)
    check(state_lib.jitted_create(pcfg), "create",
          jax.ShapeDtypeStruct((N_OBJS, DIM), jnp.float32))
    # the baseline planes' plan and execute programs
    for plan_fn, exec_fn, execute in (
            (baselines.jitted_plan_paging, baselines.jitted_execute_paging,
             "execute_paging_access"),
            (baselines.jitted_plan_object, baselines.jitted_execute_object,
             "execute_object_access")):
        check(plan_fn(pcfg), "plan_access", s, ids)
        check(exec_fn(pcfg), execute, s, ids,
              jax.eval_shape(plan_fn(pcfg), s, ids))

    # the sharded engine on the single-device oracle
    eng = engines("sharded")
    s, sids = eng.state, ids.reshape(2, BATCH // 2)
    check(eng._access, "access", s, sids)

    # the programs a far mesh runs, on a mesh of one device
    mesh = make_far_mesh(1)
    scfg = shardplane.make_config(_pcfg(), 1, BATCH)
    data = jax.ShapeDtypeStruct((N_OBJS, DIM), jnp.float32)
    s = jax.eval_shape(functools.partial(shardplane.create, scfg), data)
    sids = jax.ShapeDtypeStruct((1, BATCH), jnp.int32)
    check(shardplane.jitted_create(scfg, mesh), "create", data)
    check(shardplane.jitted_access(scfg, mesh=mesh), "sharded_access",
          s, sids)
    check(shardplane.jitted_access_degmask(scfg, mesh=mesh),
          "sharded_access_degmask", s, sids,
          jax.ShapeDtypeStruct((1,), bool))
    check(shardplane.jitted_evacuate(scfg, mesh=mesh), "sharded_evacuate", s)
    check(shardplane.jitted_advance_epoch(scfg, mesh), "sharded_advance_epoch",
          s)
    assert not [m for m in seen if "unknown" in m or "lambda" in m]
