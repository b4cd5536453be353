import numpy as np

import traffic

N = 1 << 20


def test_scrambled_zipfian_is_bounded_at_one_mebi_keys():
    ids = traffic.scrambled_zipfian(traffic.rng_for(0, 0), N, 200_000)
    assert ids.min() >= 0 and ids.max() < N
    _, counts = np.unique(ids, return_counts=True)
    top = counts.max() / ids.size
    # YCSB's top key takes 1 / zeta(10^10, 0.99) of the draws; the program's
    # clipped numpy zipf put 44% on the last id at this size
    assert abs(top - 1 / traffic.YCSB_ZETAN) < 0.002
    assert top < 0.05
    # the hash spreads the mass: no id range holds the bulk
    hist = np.bincount(ids // (N // 16), minlength=16) / ids.size
    assert hist.max() < 0.2


def test_zipfian_rank_shares_follow_ycsb_formula():
    r = traffic.zipfian_ranks(traffic.rng_for(3, 0), 400_000)
    p0 = 1 / traffic.YCSB_ZETAN
    p1 = 0.5 ** 0.99 / traffic.YCSB_ZETAN
    assert abs((r == 0).mean() - p0) < 0.002
    assert abs((r == 1).mean() - p1) < 0.0015


def _fnv_java(val: int) -> int:
    # YCSB Utils.fnvhash64, long arithmetic written out one byte at a time
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    signed = h - (1 << 64) if h >> 63 else h
    return abs(signed)


def test_fnv_matches_ycsb():
    vals = np.array([0, 1, 255, 2**31 + 5, 9_999_999_999], np.int64)
    got = traffic.fnv1a64(vals)
    assert [int(g) for g in got] == [_fnv_java(int(v)) for v in vals]


def test_scan_lengths_uniform_1_to_100():
    cfg = {"recordcount": N, "operation": "scan", "maxscanlength": 100,
           "requestdistribution": "zipfian",
           "scanlengthdistribution": "uniform"}
    reqs = traffic.Requests(cfg, 2**31 + 77, 2).next(100_000)
    lens = np.array([len(r) for r in reqs if r[0] + 100 <= N])
    assert lens.min() == 1 and lens.max() == 100
    counts = np.bincount(lens, minlength=101)[1:]
    expect = lens.size / 100
    chi2 = float((((counts - expect) ** 2) / expect).sum())
    assert chi2 < 150          # 99 degrees of freedom, p ~ 0.0006
    assert abs(lens.mean() - 50.5) < 0.5
    for r in reqs[:1000]:
        assert np.all(np.diff(r) == 1)


def test_same_seed_same_requests_other_seed_other():
    cfg = {"recordcount": N, "operation": "read",
           "requestdistribution": "zipfian"}
    a = np.concatenate(traffic.Requests(cfg, 2**40 + 1, 2).next(1000))
    b = np.concatenate(traffic.Requests(cfg, 2**40 + 1, 2).next(1000))
    c = np.concatenate(traffic.Requests(cfg, 1, 2).next(1000))
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_open_arrivals_fixed_count_per_seed():
    a = traffic.open_arrivals(5, 1000.0, 2.0)
    b = traffic.open_arrivals(2**33, 1000.0, 2.0)
    assert len(a) == len(b) == 2000
    assert np.all(np.diff(a) >= 0) and a[-1] < 2.0


def test_load_order_interleaves_shards_in_ascending_ids():
    import bench
    one = bench.load_order(64, 1, 16)
    assert (one == np.arange(64)).all()
    four = bench.load_order(64, 4, 16).reshape(-1, 4, 4)
    # each tick of 16 ids loads 4 consecutive ids of each shard's range
    assert (four[0] == [[0, 1, 2, 3], [16, 17, 18, 19], [32, 33, 34, 35],
                        [48, 49, 50, 51]]).all()
    assert sorted(four.reshape(-1)) == list(range(64))


def test_stretch_holds_whole_evacuation_rounds(monkeypatch):
    import jax
    import devtrace
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    from collections import namedtuple
    Stats = namedtuple("Stats", "hits page_outs")
    now = {"ticks": 0}

    def snapshot():                 # per-shard counters as they stand
        k = now["ticks"]
        return Stats(np.array([k, 2 * k]), np.array([1, k // 8]))

    s = devtrace.Stretch(20.0, 64, snapshot)
    s(4.9, 10)                      # before a quarter of the window
    assert not calls and s.counters() is None
    now["ticks"] = 11
    s(5.0, 11)                      # starts at the first tick after it
    s(6.0, 11 + 127)
    assert [c[0] for c in calls] == ["start"]
    now["ticks"] = 11 + 128
    s(6.1, 11 + 128)                # two rounds of 64 ticks submitted
    assert [c[0] for c in calls] == ["start", "stop"]
    s(7.0, 500)                     # only once
    assert len(calls) == 2
    assert s.counters() == ({"hits": 3 * 128, "page_outs": 139 // 8 - 1},
                            128)
