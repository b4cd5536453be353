"""``correct`` must come out false for the control and for each fault a
cell can have, planted underneath the timed path after the warm-up; and
true for the program as it is."""
import jax
import jax.numpy as jnp
import pytest

import bench
import rehearse
from conftest import ROOT

SEED = 2**31 + 4242


def _plant(monkeypatch, fault):
    """Break the engine's device programs once the warm-up is done."""
    warm = bench.warm_up

    def warm_then_break(eng, *a, **k):
        n = warm(eng, *a, **k)
        fault(eng)
        return n
    monkeypatch.setattr(bench, "warm_up", warm_then_break)


def _mask_half(ids):
    r = ids.shape[-1]
    return jnp.where(jnp.arange(r) >= r // 2, -1, ids)


def state_unchanged(eng):
    if eng._access is not None:
        acc = eng._access
        eng._access = lambda s, ids: (s, acc(s, ids)[1])
    else:
        ex = eng._exec
        eng._exec = lambda s, ids, plan: (s, ex(s, ids, plan)[1])


def half_batch(eng):
    if eng._access is not None:
        acc = eng._access
        eng._access = lambda s, ids: acc(s, _mask_half(ids))
    else:
        plan, ex = eng._plan, eng._exec
        eng._plan = lambda s, ids: plan(s, _mask_half(ids))
        eng._exec = lambda s, ids, p: ex(s, _mask_half(ids), p)


def altered(eng):
    if eng._access is not None:
        acc = eng._access

        def f(s, ids):
            s, rows = acc(s, ids)
            return s, rows.at[0, 0, 0].add(1.0)
        eng._access = f
    else:
        ex = eng._exec

        def g(s, ids, p):
            s, rows = ex(s, ids, p)
            return s, rows.at[0, 0].add(1.0)
        eng._exec = g


def _no_exchange(monkeypatch):
    from repro.core import shardplane

    def fault(eng):
        monkeypatch.setattr(shardplane, "_a2a", lambda x: x)
        mesh = eng.state.slab.sharding.mesh
        eng._access = shardplane._jitted_access.__wrapped__(
            eng.scfg, eng.cfg.mode, mesh, False, False)
    return fault


@pytest.fixture(scope="module")
def root():
    """The benchmark as committed; its sharded cell runs here on four CPU
    devices."""
    return ROOT


@pytest.mark.parametrize("workload", ["ycsb_c.closed", "ycsb_c_x4.closed"])
def test_sound_run_is_correct(root, workload):
    out = rehearse.rehearse(workload, seed=SEED, seconds=1.0, root=root)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "metrics" not in out
    # the warm state: the frame pool full and pages leaving it
    assert out["rehearsal"]["warm"]["page_outs"] > 0


def test_control_bf16_is_not_correct():
    out = rehearse.rehearse("ycsb_c.closed", seed=SEED, seconds=1.0,
                            control="bf16")
    assert not out["correct"]
    c = out["checks"]["mismatched_rows"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("workload", ["ycsb_c.closed", "ycsb_c_x4.closed"])
@pytest.mark.parametrize("fault", [state_unchanged, half_batch, altered],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(root, monkeypatch, workload, fault):
    _plant(monkeypatch, fault)
    out = rehearse.rehearse(workload, seed=SEED, seconds=1.0, root=root)
    assert not out["correct"], out["checks"]


def test_exchange_left_out_is_not_correct(root, monkeypatch):
    _plant(monkeypatch, _no_exchange(monkeypatch))
    out = rehearse.rehearse("ycsb_c_x4.closed", seed=SEED, seconds=1.0,
                            root=root)
    assert not out["correct"], out["checks"]
    assert jax.device_count() >= 4
