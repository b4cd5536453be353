"""Each metric's reduction, on a hand-made trace and run record, and on a
small trace recorded on a TPU v5e kept beside this file."""
import glob
import os
from types import SimpleNamespace

import numpy as np
import pytest

import bench
import devtrace
from conftest import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = bench.Spec.load(ROOT)
PEAKS = SPEC.peaks("TPU v5 lite")
MS = 1_000_000          # ns


def _hand_trace(exec_ms=(30,), submits=4):
    # stretch [0, 200 ms); four ticks of plan 2 ms + execute 30 ms (or
    # programs of ``exec_ms`` one after another), one evacuation of 10 ms
    # after the second; ops overlap inside modules; the host submits each
    # tick 1 ms before it starts
    plan, evac = "jit__unknown(1)", "jit__unknown(3)"
    mods, ops, spans, t = [], [], [], 0
    for k in range(4):
        mods.append([plan, t, 2 * MS])
        s = t + 2 * MS
        for j, ms in enumerate(exec_ms):
            mods.append([f"jit__unknown({10 + j})", s, ms * MS])
            s += ms * MS
        ops += [["fusion.1", t, 2 * MS], ["copy.2", t + 2 * MS, 20 * MS],
                ["gather_rows", t + 20 * MS, 12 * MS]]   # overlaps copy.2
        if k < submits:
            spans.append(["cb.submit", max(t - MS, 0), MS])
        t += 40 * MS
        if k == 1:
            mods.append([evac, t, 10 * MS])
            ops.append(["fusion.3", t, 10 * MS])
            t += 10 * MS
    spans += [[devtrace.STRETCH, 0, 200 * MS], ["cb.wait", 32 * MS, 8 * MS],
              ["cb.wait", 162 * MS, 38 * MS]]
    return devtrace.Trace({0: {"XLA Modules": mods, "XLA Ops": ops}}, spans)


def _rec(trace=None, **kw):
    stats = dict.fromkeys(bench.STATS, 0)
    stats.update(hits=900, misses=100, page_ins=30, obj_ins=40,
                 dirty_page_outs=5)
    rec = dict(trace=trace, stats=stats, stretch=(dict(stats), 20),
               row_bytes=1024, page_bytes=8192,
               ids=1000, ticks=20, requests=1000, window_s=10.0,
               lat_ms=np.arange(1, 101, dtype=np.float64),
               good_in_window=990, setup_s=12.5, peaks=PEAKS)
    rec.update(kw)
    return SimpleNamespace(**rec)


def read(name, rec):
    return SPEC.reader(name)(rec)


def test_union_busy_and_idle():
    t = _hand_trace()
    assert t.window_s == pytest.approx(0.2)
    # four ticks of 32 ms and one 10 ms evacuation: 138 ms busy
    assert t.busy_s(0) == pytest.approx(0.138)
    assert read("device_idle_share", _rec(t)) == pytest.approx(31.0)
    gaps = dict(t.idle_gaps())
    # idle: 8 ms after three ticks and 38 ms at the end of the stretch; the
    # first and the last gap lie in host spans, the other two in none
    assert sum(gaps.values()) == pytest.approx(0.062)
    assert gaps["cb.wait"] == pytest.approx(0.008 + 0.038)
    assert gaps["no span"] == pytest.approx(0.016)


def test_program_roles_and_times_per_tick():
    t = _hand_trace()
    assert sorted(t.roles(0).values()) == ["exec", "maint", "plan"]
    r = _rec(t)
    assert read("plan_ms_per_tick", r) == pytest.approx(2.0)
    assert read("exec_ms_per_tick", r) == pytest.approx(30.0)
    assert read("maint_ms_per_tick", r) == pytest.approx(2.5)
    top = t.top_ops(2)
    assert top[0][0] == "copy.2" and top[0][1] == pytest.approx(0.08)


def test_roles_refuse_a_split_execute():
    # a later change splits the execute program in two of like size: the
    # frequency reading can no longer tell the executor, and says so
    t = _hand_trace(exec_ms=(16, 14))
    with pytest.raises(ValueError, match="dominates"):
        t.roles(0)
    with pytest.raises(ValueError):
        read("exec_ms_per_tick", _rec(t))


def test_roles_refuse_programs_that_do_not_match_ticks():
    # four execute runs for no more than the first submitted tick
    t = _hand_trace()
    t.spans = [s for s in t.spans if s[0] != "cb.submit"][:2] + \
        [["cb.submit", 0, MS]] * 8
    with pytest.raises(ValueError, match="submitted ticks"):
        t.roles(0)
    assert _hand_trace().submits() == 4


def test_exchange_on_the_busiest_chip():
    one = _hand_trace()
    assert read("exchange_ms_per_tick", _rec(one)) is None
    devs = {}
    for d, coll_ms in ((0, 3), (1, 5)):
        t = _hand_trace()
        ev = t.devices[0]
        ev["XLA Ops"] = ev["XLA Ops"] + [
            ["all-to-all.7", k * 40 * MS + 25 * MS, coll_ms * MS]
            for k in range(4)] + [["%all-gather-start.2 = (s32[64]) "
                                   "all-gather-start(...)", 5 * MS, MS]]
        devs[d] = ev
    t = devtrace.Trace(devs, one.spans)
    # device 1: four all-to-alls of 5 ms and one 1 ms all-gather, 4 ticks
    assert read("exchange_ms_per_tick", _rec(t)) == pytest.approx(5.25)


def test_exec_roofline_from_least_bytes():
    r = _rec(_hand_trace())
    least = (2 * 1024 * 1000 + 2 * 8192 * 35 + 2 * 1024 * 40) / 20
    want = 100 * least / 819e9 / 0.030
    assert read("exec_roofline", r) == pytest.approx(want)
    assert 0 < want < 100


def test_counter_metrics():
    r = _rec()
    assert read("hit_ratio", r) == pytest.approx(90.0)
    assert read("paging_byte_share", r) == pytest.approx(
        100 * 30 * 8192 / (30 * 8192 + 40 * 1024))
    assert read("far_bytes_per_req", r) == pytest.approx(
        (30 * 8192 + 40 * 1024 + 5 * 8192) / 1000)
    assert read("p50_ms", r) == pytest.approx(50.5)
    assert read("p99_ms", r) == pytest.approx(99.01)
    assert read("goodput_rps", r) == pytest.approx(99.0)
    assert read("setup_s", r) == 12.5


def test_nothing_to_read_gives_nothing():
    r = _rec(None, ticks=0)
    for name in ("device_idle_share", "plan_ms_per_tick", "exec_ms_per_tick",
                 "exec_roofline", "maint_ms_per_tick"):
        assert read(name, r) is None, name
    empty = _rec(devtrace.Trace({}, []))
    assert read("exec_roofline", empty) is None
    assert read("exec_roofline", _rec(_hand_trace(), stretch=None)) is None
    no_ingress = _rec()
    no_ingress.stats.update(page_ins=0, obj_ins=0)
    assert read("paging_byte_share", no_ingress) is None


def test_every_metric_has_a_reader():
    for group in ("end_to_end", "per_layer"):
        for m in SPEC.bench[group]:
            assert callable(SPEC.reader(m["name"])), m["name"]


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "*.trace.json.gz")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_chip_trace(path):
    t = devtrace.Trace.load(path)
    r = _rec(t)
    busy = t.mean_busy_s()
    assert 0 < busy <= t.window_s
    idle = read("device_idle_share", r)
    assert 0 <= idle < 100
    ex = read("exec_ms_per_tick", r)
    assert ex > 0 and read("plan_ms_per_tick", r) > 0
    # runs that start in the stretch, the last perhaps ending past it
    n = t.role_count(0, "exec")
    assert n > 0 and ex * (n - 1) <= 1e3 * t.window_s
    r = _rec(t)
    r.stretch = (r.stats, n)
    assert 0 < read("exec_roofline", r) < 100
    assert t.top_ops(10) and t.idle_gaps(10)
    # the exchange exists only across chips
    ex = read("exchange_ms_per_tick", r)
    if len(t.devices) > 1:
        assert 0 < ex < read("exec_ms_per_tick", r)
    else:
        assert ex is None
