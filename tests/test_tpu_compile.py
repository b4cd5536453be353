"""Compile the Pallas kernels for a described TPU v5e chip.

Interpret mode accepts block shapes that Mosaic refuses (a block's last
two dims must be divisible by the (sublane, lane) tiling or equal the
array's), so these tests lower and compile each kernel of the main path
with the TPU compiler, at the widths ``chip_smoke.py`` serves: 1,048,576
records of 256 f32 in 8-record pages (393,216 virtual pages, 32,768
frames), batch 64; and the kvplane kernels at B=8, KVH=8, G=4, Dh=128,
16-token pages in bf16.  Nothing runs: a compile needs shapes only.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one given this file
loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

V, P, D, F, R = 393216, 8, 256, 32768, 64
B, KVH, G, DH, PT, NPG, FR = 8, 8, 4, 128, 16, 64, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


CASES = {
    # runtime-path object ingress: rows of the [V*P, D] slab
    "gather_rows": (
        lambda pool, idx: ops.gather_rows(pool, idx, impl="pallas"),
        [((V * P, D), jnp.float32), ((R,), jnp.int32)]),
    # paging-path ingress: whole [P, D] pages of the slab
    "gather_pages": (
        lambda slab, idx: ops.gather_pages(slab[None], idx, impl="pallas",
                                           masked=False),
        [((V, P, D), jnp.float32), ((R,), jnp.int32)]),
    # evacuator page assembly over the frame pool (4 destination pages)
    "compact_pages": (
        lambda pool, plan: ops.compact_pages(pool, plan, page_objs=P,
                                             impl="pallas"),
        [((F * P, D), jnp.float32), ((4 * P,), jnp.int32)]),
    # epoch governor's CAR decay over every virtual page
    "cat_decay": (
        lambda cat, ema, alloc: ops.cat_decay(cat, ema, alloc, decay=0.5,
                                              impl="pallas"),
        [((V, P), jnp.bool_), ((V,), jnp.float32), ((V,), jnp.int32)]),
    "paged_attention": (
        lambda q, k, v, pt, ln: ops.paged_attention(q, k, v, pt, ln,
                                                    impl="pallas"),
        [((B, KVH * G, DH), jnp.bfloat16), ((KVH, FR, PT, DH), jnp.bfloat16),
         ((KVH, FR, PT, DH), jnp.bfloat16), ((B, NPG), jnp.int32),
         ((B, NPG), jnp.int32)]),
    "page_scores": (
        lambda q, kmax, kmin: ops.page_scores(q, kmax, kmin, impl="pallas"),
        [((B, KVH * G, DH), jnp.bfloat16), ((KVH, 256, DH), jnp.bfloat16),
         ((KVH, 256, DH), jnp.bfloat16)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
