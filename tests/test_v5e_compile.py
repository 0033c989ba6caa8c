"""Compiles, for a described TPU v5e and at the sizes the benchmark runs,
what no interpret-mode test can refuse: the three flash kernels at head
width 256 (VMEM) and at the ring's call shapes on four chips, and the
expert layer's grouped products (XLA's own ragged-dot kernels). Nothing
runs and no time is read; a compile that passes is not a chip run. Every
such test lives in this one file, and the topology is described inside a
fixture: one process at a time may load the TPU's library
(on-chip-measurement guide, section 2)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it.
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("bh,s", [(160, 2048), (40, 8192)])
def test_flash_kernels_at_width_256_fit_the_chip(one_chip, no_compile_cache,
                                                 bh, s):
    """The MLA block's calls: 20 heads of 256 over 16,384 tokens, at the
    block defaults the call's shape selects (1024 x 1024)."""
    from ddstore_tpu.ops.attention import flash_attention

    q = jax.ShapeDtypeStruct((1, bh, s, 256), jnp.bfloat16,
                             sharding=one_chip)

    def f(q, k, v):
        out, _ = flash_attention(q, k, v, causal=True, interpret=False)
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(q, q, q) \
        .compile().as_text()
    for kernel in ("ddstore_flash_fwd", "ddstore_flash_dq",
                   "ddstore_flash_dkv"):
        assert kernel in text


@pytest.mark.parametrize("causal,bh,s", [(True, 16, 16384),
                                         (False, 32, 8192)],
                         ids=["step0-causal", "stripe-pairs"])
def test_the_ring_calls_of_the_four_chip_cell_fit_the_chip(
        one_chip, no_compile_cache, causal, bh, s):
    """A chip's two flash calls a layer in ``dense-lm-d1024.s32k.dp2sp2``
    (PR 28): the local 16,384 rows causally, then two stacked 8,192 x
    8,192 stripe pairs unmasked, at head width 64 and the blocks the
    calls' shapes select."""
    from ddstore_tpu.ops.attention import flash_attention

    q = jax.ShapeDtypeStruct((1, bh, s, 64), jnp.bfloat16, sharding=one_chip)

    def f(q, k, v):
        out, _ = flash_attention(q, k, v, causal=causal, interpret=False)
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(q, q, q) \
        .compile().as_text()
    for kernel in ("ddstore_flash_fwd", "ddstore_flash_dq",
                   "ddstore_flash_dkv"):
        assert kernel in text


def test_expert_layer_lowers_to_grouped_products(one_chip, no_compile_cache):
    """Published widths, a quarter of a step's tokens: the grouped products
    are XLA's ragged-dot kernels, which size their grid from the rows each
    expert got. The first trip over the sorted rows and the loop's body
    for the others each hold the forward's three, and in the backward rule
    the forward's three again beside the transposes' six; XLA may share
    the in-line trip's three between the two rules (here, where nothing is
    rematerialised, it does)."""
    from ddstore_tpu.models.moe import SharedRoutedMoe, routed_chunk

    layer = SharedRoutedMoe(64, 4, 1536, share=(0, 8), scaling=1.8)
    x = jax.ShapeDtypeStruct((4096, 2048), jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(layer.init, jax.random.key(0), x)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)

    def f(p, x):
        y, load = layer.apply(p, x)
        return (y.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(f, argnums=(0, 1))).lower(params, x) \
        .compile().as_text()
    assert routed_chunk(4096, 4, 8, 64) == 3072     # of 16,384 pairs
    assert text.count('op_name="ragged-dot-none"') in (2 * (3 + 9) - 3,
                                                       2 * (3 + 9))
