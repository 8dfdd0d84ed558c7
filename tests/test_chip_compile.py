"""The main path's Pallas kernels, compiled for a TPU v5e at sd_v1_4's
widths without a chip attached, plus interpret-mode checks of the fused
GroupNorm+swish at its channels-per-group.

The kernels are called with ``interpret=False`` directly, or through
``ops`` with ``mode='pallas'``: left to itself, ``ops`` takes its CPU
branch here.  The topology is described only inside the ``topo``
fixture (never at import), and every test that needs it lives in this one
file, so under pytest-xdist exactly one worker loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, ref
from repro.kernels.fused_gn_swish import fused_gn_swish_kernel
from repro.kernels.w8a8_matmul import w8a8_matmul_kernel

#: fused_gn_swish shapes: sd_v1_4's largest GroupNorm input at 8 slots
#: (64x64, 1020 channels, 30 groups of 34) and its widest (8x8, 2720
#: channels, 32 groups of 85)
GN_SHAPES = [((8, 64, 64, 1020), 30), ((8, 8, 8, 2720), 32)]


@pytest.fixture(scope='module')
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', was)
    cc.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize('shape,groups', GN_SHAPES)
def test_fused_gn_swish_compiles_for_v5e(one_chip, no_compile_cache,
                                         shape, groups):
    C = shape[-1]
    exe = _compile(
        one_chip,
        lambda x, s, b: fused_gn_swish_kernel(x, s, b, groups=groups),
        (shape, jnp.float32), ((C,), jnp.float32), ((C,), jnp.float32))
    assert 'tpu_custom_call' in exe.as_text()


def test_w8a8_matmul_compiles_for_v5e(one_chip, no_compile_cache):
    """sd_v1_4's 32x32 self-attention projection at 4 slots: 4096 tokens,
    680 -> 680 channels, padded to 128 as ``ops.w8a8_matmul`` pads."""
    M, K, N = 4 * 32 * 32, 768, 768
    exe = _compile(
        one_chip, lambda xq, xs, wq, ws: w8a8_matmul_kernel(
            xq, xs, wq, ws, bm=128),
        ((M, K), jnp.int8), ((M, 1), jnp.float32), ((K, N), jnp.int8),
        ((1, N), jnp.float32))
    assert 'tpu_custom_call' in exe.as_text()


@pytest.mark.parametrize('shape', [
    (16, 8, 1024, 85),      # t2i_unet_860m 32x32 self-attention, 16 slots
    (16, 8, 256, 170),      # t2i_unet_860m 16x16
    (2048, 1, 256, 256),    # ddpm_cifar10_ch128 16x16, 2048 slots
])
def test_flash_attention_compiles_for_v5e(one_chip, no_compile_cache,
                                          shape):
    """The cells' self-attention shapes, (B, heads, S, d) with bf16 q/k/v
    as ``models/unet._mha`` makes them (it keeps DDPM's, where T = d, on
    the einsum): the blocks ``ops`` chooses lower for the chip and fit
    its VMEM."""
    exe = _compile(
        one_chip, lambda q, k, v: ops.flash_attention(q, k, v, scale=1.0,
                                                      mode='pallas'),
        *[(shape, jnp.bfloat16)] * 3)
    assert 'tpu_custom_call' in exe.as_text()


@pytest.mark.parametrize('shape,groups', [
    ((2, 8, 8, 340), 20),        # 17 channels per group, one row tile
    ((1, 16, 64, 1020), 30),     # 34 channels per group, four row tiles
])
def test_fused_gn_swish_interpret_matches_ref(shape, groups):
    rng = np.random.default_rng(0)
    C = shape[-1]
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    scale = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    out = fused_gn_swish_kernel(x, scale, bias, groups=groups,
                                interpret=True)
    exp = ref.gn_swish_ref(x, scale, bias, groups=groups)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)
