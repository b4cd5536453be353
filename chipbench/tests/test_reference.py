import numpy as np

import records
import reference


def test_reference_is_a_function_of_seed_and_id():
    ids = np.array([0, 5, 1 << 20, (1 << 22) - 1])
    a = reference.rows(2**31 + 9, ids, 256)
    b = reference.rows(2**31 + 9, ids[::-1], 256)[::-1]
    assert np.array_equal(a.view(np.uint32), b.view(np.uint32))
    # one id alone reads the same as among others
    c = reference.rows(2**31 + 9, ids[2:3], 256)
    assert np.array_equal(a[2:3].view(np.uint32), c.view(np.uint32))
    d = reference.rows(2**31 + 10, ids, 256)
    assert not np.any(np.all(a == d, axis=1))
    # seeds past 32 bits change the values too
    e = reference.rows(2**31 + 9 + 2**32, ids, 256)
    assert not np.any(np.all(a == e, axis=1))
    assert a.dtype == np.float32 and a.min() >= 0 and a.max() < 1


def test_device_build_equals_reference():
    for seed in (0, 2**31 + 123, 2**35 + 1):
        got = np.asarray(records.build(seed, 3000, 256))
        want = reference.rows(seed, np.arange(3000), 256)
        assert np.array_equal(got.view(np.uint32), want.view(np.uint32))


def test_bfloat16_control_changes_nearly_every_row():
    ids = np.arange(5000)
    want = reference.rows(7, ids, 256)
    low = reference.to_bfloat16(want)
    assert reference.mismatches(7, ids, low).all()
    assert not reference.mismatches(7, ids, want).any()
    assert np.max(np.abs(low - want)) < 2.0 ** -8
