"""The serving engine donates the plane state to the programs that return it.

The engine holds one live state, so each state-returning program it
dispatches (execute or sharded access, evacuation, epoch) takes the state's
buffers for its result: XLA writes the new state over the old one and
copies no slab, and the runtime allocates no fresh state per tick.  The
counters (``state.stats``) are passed apart and not donated, so a snapshot
of them stays readable after later ticks.  Checked on the one-chip planes,
the sharded engine on the single-device oracle and the far mesh's programs
on a mesh of one device, against the library's non-donating programs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, plane as plane_lib, shardplane
from repro.core.layout import PlaneConfig
from repro.launch.mesh import make_far_mesh
from repro.serving.engine import Engine, EngineConfig

N_OBJS, DIM, BATCH = 256, 8, 16
EVAC_EVERY, EPOCH_EVERY, TICKS = 4, 2, 16

ENGINES = {
    "hybrid": dict(),
    "paging": dict(plane="paging"),
    "object": dict(plane="object"),
    "sharded": dict(shards=2),
}
KINDS = sorted(ENGINES) + ["mesh"]


def _pcfg():
    return PlaneConfig(num_objs=N_OBJS, obj_dim=DIM, page_objs=8,
                       num_frames=12, num_vpages=3 * (N_OBJS // 8))


def _data():
    return jnp.arange(N_OBJS * DIM, dtype=jnp.float32).reshape(N_OBJS, DIM)


class _MeshTicks:
    """The far mesh's programs on a mesh of one device, dispatched tick by
    tick on the engine's schedule (the engine's sharded path needs two or
    more shards)."""

    def __init__(self, donate: bool):
        mesh = make_far_mesh(1)
        scfg = shardplane.make_config(_pcfg(), 1, BATCH)
        self.state = shardplane.jitted_create(scfg, mesh)(_data())
        self._access = shardplane.jitted_access(scfg, mesh=mesh,
                                                donate=donate)
        self._access_degmask = shardplane.jitted_access_degmask(
            scfg, mesh=mesh, donate=donate)
        self._evac = shardplane.jitted_evacuate(scfg, mesh=mesh,
                                                donate=donate)
        self._epoch = shardplane.jitted_advance_epoch(scfg, mesh,
                                                      donate=donate)
        self.ticks = 0

    def submit(self, ids):
        self.state, rows = self._access(self.state,
                                        jnp.asarray(ids).reshape(1, BATCH))
        self.ticks += 1
        if self.ticks % EVAC_EVERY == 0:
            self.state = self._evac(self.state)
        if self.ticks % EPOCH_EVERY == 0:
            self.state = self._epoch(self.state)
        return rows.reshape(BATCH, DIM)

    def drain(self):
        jax.block_until_ready(self.state)


def _library_programs(eng):
    """Swap the engine's state-returning programs for the library's
    non-donating ones."""
    cfg = eng.cfg
    if eng.scfg is not None:
        eng._access = shardplane.jitted_access(eng.scfg, cfg.mode)
        eng._evac = shardplane.jitted_evacuate(eng.scfg)
        eng._epoch = shardplane.jitted_advance_epoch(eng.scfg)
        return
    execute = {"hybrid": plane_lib.jitted_execute_access,
               "paging": baselines.jitted_execute_paging,
               "object": baselines.jitted_execute_object}[cfg.plane]
    eng._exec = execute(eng.pcfg, cfg.mode)
    if cfg.plane == "hybrid":
        eng._evac = plane_lib.jitted_evacuate(eng.pcfg)
        eng._epoch = plane_lib.jitted_advance_epoch(eng.pcfg)


def _build(kind, donate=True):
    if kind == "mesh":
        return _MeshTicks(donate)
    eng = Engine(EngineConfig(batch=BATCH, evac_every=EVAC_EVERY,
                              epoch_every=EPOCH_EVERY, **ENGINES[kind]),
                 _pcfg(), _data())
    if not donate:
        _library_programs(eng)
    return eng


def _ids(rng):
    return rng.randint(0, N_OBJS, BATCH).astype(np.int32)


def _served(stats) -> int:
    """Ids the plane has counted (every real id is a hit or a miss)."""
    return int(np.sum(stats.hits) + np.sum(stats.misses))


def _state_programs(eng):
    """``(program, args, fresh)`` of each state-returning program ``eng``
    dispatches, ``fresh`` the outputs it cannot write over its donated
    state: its results besides the state (rows; the served flags of the
    breaker's program) and the one counter array."""
    s = eng.state
    ids = jnp.zeros((BATCH,), jnp.int32)
    if eng._access is not None:
        ids = ids.reshape(s.step.shape[0], -1)
        out = [(eng._access, (s, ids), 2)]
        if getattr(eng, "_access_degmask", None) is not None:
            deg = jnp.zeros(s.step.shape, bool)
            out.append((eng._access_degmask, (s, ids, deg), 3))
    else:
        out = [(eng._exec, (s, ids, jax.eval_shape(eng._plan, s, ids)), 2)]
    if eng._evac is not None:
        out += [(eng._evac, (s,), 1), (eng._epoch, (s,), 1)]
    return out


@pytest.mark.parametrize("kind", KINDS)
def test_submit_deletes_the_previous_state(kind):
    eng = _build(kind)
    rng = np.random.RandomState(1)
    # through an evacuation and two epochs on the hybrid plane
    for tick in range(1, EVAC_EVERY + 1):
        old = eng.state
        eng.submit(_ids(rng))
        assert old.slab.is_deleted() and old.frames.is_deleted(), tick
        assert not old.stats.counts.is_deleted(), tick
    eng.drain()


@pytest.mark.parametrize("kind", KINDS)
def test_stats_snapshot_reads_after_later_ticks(kind):
    eng = _build(kind)
    rng = np.random.RandomState(2)
    for _ in range(4):
        eng.submit(_ids(rng))
    snap = eng.state.stats            # device arrays, read only at the end
    for _ in range(8):
        eng.submit(_ids(rng))
    eng.drain()
    assert _served(jax.device_get(snap)) == 4 * BATCH
    assert _served(jax.device_get(eng.state.stats)) == 12 * BATCH


@pytest.mark.parametrize("kind", KINDS)
def test_state_programs_alias_the_whole_state(kind):
    """Every donated buffer is aliased to an output (none is left unusable,
    which JAX would warn of), the slab and the frame pool among them, and
    the runtime allocates only the outputs left: the results besides the
    state and the counters, one array of them."""
    eng = _build(kind)
    s = eng.state
    donated = jax.tree.leaves(s._replace(stats=None))
    donated_bytes = sum(x.nbytes for x in donated)
    assert donated_bytes > s.slab.nbytes + s.frames.nbytes
    for program, args, fresh in _state_programs(eng):
        lowered = program.lower(*args)
        aliased = lowered.as_text().count("tf.aliasing_output")
        assert aliased == len(donated)
        outputs = len(jax.tree.leaves(lowered.out_info))
        assert outputs - aliased == fresh, program
        memory = lowered.compile().memory_analysis()
        assert memory.alias_size_in_bytes == donated_bytes


@pytest.mark.parametrize("kind", KINDS)
def test_a_donated_state_raises_before_dispatch(kind):
    """A program handed a state it already took raises at once, before
    any device runs it, and the engine serves on."""
    eng = _build(kind)
    rng = np.random.RandomState(4)
    old = eng.state
    eng.submit(_ids(rng))
    for program, args, _ in _state_programs(eng):
        with pytest.raises(ValueError, match="donated"):
            program(old, *args[1:])
    ids = _ids(rng)
    rows = eng.submit(ids)
    eng.drain()
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(_data())[ids])


@pytest.mark.parametrize("kind", KINDS)
def test_donating_ticks_match_the_library_programs(kind):
    eng, ref = _build(kind), _build(kind, donate=False)
    first = ref.state
    rng = np.random.RandomState(3)
    rows, want = [], []
    for _ in range(TICKS):
        ids = _ids(rng)
        rows.append(eng.submit(ids))
        want.append(ref.submit(ids))
    eng.drain()
    ref.drain()
    assert not first.slab.is_deleted()          # the library's are functional
    for tick, (got, exp) in enumerate(zip(rows, want), 1):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp),
                                      err_msg=f"rows of tick {tick}")
    for field in eng.state._fields:
        for x, y in zip(jax.tree.leaves(getattr(eng.state, field)),
                        jax.tree.leaves(getattr(ref.state, field))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"PlaneState.{field}")
