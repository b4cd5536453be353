"""The plane's counters travel as one int32 array (``PlaneStats.counts``,
``[18]`` or ``[S, 18]`` stacked over shards) and read on the host as the
named fields they were: ``bump`` adds per field, ``device_get(stats)
._asdict()`` gives every field in ``PlaneStats._fields`` order with its
per-field shape, and ``shardplane.stats_total`` sums the shards."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import shardplane
from repro.core import state as state_lib
from repro.core.layout import PlaneConfig
from repro.core.state import PlaneStats
from repro.serving.engine import Engine, EngineConfig

N_OBJS, DIM, BATCH, SHARDS = 256, 8, 16, 2
EVAC_EVERY, EPOCH_EVERY, TICKS = 4, 2, 16
NF = len(PlaneStats._fields)


def _pcfg():
    return PlaneConfig(num_objs=N_OBJS, obj_dim=DIM, page_objs=8,
                       num_frames=12, num_vpages=3 * (N_OBJS // 8))


def _data():
    return jnp.arange(N_OBJS * DIM, dtype=jnp.float32).reshape(N_OBJS, DIM)


def _bump_adds_per_field():
    rng = np.random.RandomState(0)
    for lead in [(), (SHARDS,)]:
        base = rng.randint(0, 1 << 20, lead + (NF,)).astype(np.int32)
        names = rng.choice(PlaneStats._fields, 7, replace=False)
        # scalar deltas, bools and, on the stacked array, one per shard
        deltas = {str(k): rng.randint(0, 1000, lead).astype(np.int32)
                  for k in names[:5]}
        deltas[str(names[5])] = np.asarray(True)
        deltas[str(names[6])] = 3
        got = jax.device_get(state_lib.bump(PlaneStats(jnp.asarray(base)),
                                            **deltas))
        assert got.counts.shape == base.shape
        assert got.counts.dtype == np.int32
        for i, f in enumerate(PlaneStats._fields):
            want = base[..., i] + np.asarray(deltas.get(f, 0), np.int32)
            np.testing.assert_array_equal(getattr(got, f), want, err_msg=f)
    with pytest.raises(AttributeError, match="no counter"):
        state_lib.bump(PlaneStats.zeros(), not_a_counter=1)


def _engine_ticks(shards):
    eng = Engine(EngineConfig(batch=BATCH, evac_every=EVAC_EVERY,
                              epoch_every=EPOCH_EVERY, shards=shards),
                 _pcfg(), _data())
    rng = np.random.RandomState(5)
    report = eng.run([rng.randint(0, N_OBJS, BATCH).astype(np.int32)
                      for _ in range(TICKS)])["stats"]
    raw = jax.device_get(eng.state.stats)
    view = raw._asdict()
    assert list(view) == list(PlaneStats._fields)
    shape = (shards,) if shards > 1 else ()
    assert raw.counts.shape == shape + (NF,)
    for i, (f, v) in enumerate(view.items()):
        assert np.shape(v) == shape and np.asarray(v).dtype == np.int32, f
        np.testing.assert_array_equal(v, raw.counts[..., i], err_msg=f)
        np.testing.assert_array_equal(v, getattr(raw, f), err_msg=f)
        np.testing.assert_array_equal(
            v, getattr(eng.state.stats, f), err_msg=f)
    assert int(np.sum(raw.hits) + np.sum(raw.misses)) == TICKS * BATCH
    np.testing.assert_array_equal(raw.epochs, TICKS // EPOCH_EVERY)
    assert report == {f: int(np.sum(v)) for f, v in view.items()}


def _stats_total_sums_the_shards():
    scfg = shardplane.make_config(_pcfg(), SHARDS, BATCH)
    states = shardplane.create(scfg, _data())
    access = shardplane.jitted_access(scfg)
    epoch = shardplane.jitted_advance_epoch(scfg)
    rng = np.random.RandomState(6)
    for _ in range(6):
        ids = rng.randint(0, N_OBJS, (SHARDS, BATCH))
        states, _ = access(states, jnp.asarray(ids, jnp.int32))
        states = epoch(states)
    per_shard = jax.device_get(states.stats)
    total = jax.device_get(shardplane.stats_total(states))
    assert total.counts.shape == (NF,)
    assert list(total._asdict()) == list(PlaneStats._fields)
    for f, v in total._asdict().items():
        assert np.shape(v) == ()
        assert int(v) == int(np.sum(getattr(per_shard, f))), f
    assert int(total.hits + total.misses) == 6 * SHARDS * BATCH


def _zeros_like_keeps_one_array():
    z = jax.tree.map(jnp.zeros_like, PlaneStats.zeros())
    assert isinstance(z, PlaneStats) and z.counts.shape == (NF,)
    assert len(jax.tree.leaves(z)) == 1


CASES = {
    "bump": _bump_adds_per_field,
    "engine_one_chip": lambda: _engine_ticks(1),
    "engine_sharded": lambda: _engine_ticks(SHARDS),
    "stats_total": _stats_total_sums_the_shards,
    "zeros_like": _zeros_like_keeps_one_array,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_counters_read_as_named_fields(case):
    CASES[case]()

