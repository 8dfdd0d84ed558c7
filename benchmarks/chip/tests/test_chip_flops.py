"""``flops.py`` against XLA's own counts, on the CPU at small sizes."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(HERE, '..', '..', '..', 'src'))

import flops  # noqa: E402

SMALL_UNET = dict(img_size=16, in_ch=4, base_ch=64, ch_mults=[1, 2],
                  n_res_blocks=1, attn_resolutions=[8], n_heads=4,
                  context_dim=32, groups=32, timesteps=1000)
SMALL_VAE = dict(img_size=64, in_ch=3, z_ch=4, base_ch=32, ch_mults=[1, 2, 2],
                 groups=32)


def _frozen(cls, d, **kw):
    return cls(**{k: tuple(v) if isinstance(v, list) else v
                  for k, v in d.items() if k in cls.__dataclass_fields__},
               **kw)


def _xla_flops(fn, *args):
    return jax.jit(fn).lower(*args).compile().cost_analysis()['flops']


@pytest.mark.parametrize('context', [True, False])
def test_unet_pass_flops_match_xla(context, monkeypatch):
    """Convolutions, matmuls and attention are all but the whole count:
    XLA's count (which adds normalisation and activations) is at most 3%
    above ours, never below."""
    monkeypatch.setenv('REPRO_KERNELS', 'xla')
    from repro.models.unet import UNetConfig, init_unet, unet_apply
    cfg = _frozen(UNetConfig, SMALL_UNET, name='small')
    S = jax.ShapeDtypeStruct
    p = jax.eval_shape(lambda k: init_unet(k, cfg), jax.random.PRNGKey(0))
    ctx = S((2, flops.CONTEXT_TOKENS, 32), jnp.float32) if context else None
    xla = _xla_flops(lambda p, x, t, c: unet_apply(p, cfg, x, t, c), p,
                     S((2, 16, 16, 4), jnp.float32), S((2,), jnp.int32), ctx)
    ours = flops.unet_pass(SMALL_UNET, 2, context)
    assert 0.97 * xla <= ours <= xla


def test_vae_decode_flops_match_xla():
    from repro.models.autoencoder import VAEConfig, init_vae, vae_decode
    cfg = _frozen(VAEConfig, SMALL_VAE)
    p = jax.eval_shape(lambda k: init_vae(k, cfg), jax.random.PRNGKey(0))
    xla = _xla_flops(lambda p, z: vae_decode(p, cfg, z), p,
                     jax.ShapeDtypeStruct((2, 16, 16, 4), jnp.float32))
    ours = flops.vae_decode(SMALL_VAE, 2)
    assert 0.97 * xla <= ours <= xla


@pytest.mark.parametrize('shape', [(2, 8, 8, 340), (1, 64, 64, 680),
                                   (16, 32, 32, 1020)])
def test_gn_swish_bytes_are_the_pallas_calls_operands(shape):
    """The bytes are what the kernel's two ``pallas_call``s take and give,
    read from the kernel's own jaxpr at the same shape."""
    from repro.kernels.fused_gn_swish import fused_gn_swish_kernel
    c = shape[-1]
    g = min(32, c)
    while c % g:
        g -= 1
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda x, s, b: fused_gn_swish_kernel(
        x, s, b, groups=g))(S(shape, jnp.float32), S((c,), jnp.float32),
                            S((c,), jnp.float32))
    moved = 0
    stack = [jaxpr.jaxpr]
    while stack:
        for eqn in stack.pop().eqns:
            if eqn.primitive.name == 'pallas_call':
                moved += sum(v.aval.size * v.aval.dtype.itemsize
                             for v in list(eqn.invars) + list(eqn.outvars))
                continue
            for value in eqn.params.values():
                inner = getattr(value, 'jaxpr', value)
                if hasattr(inner, 'eqns'):
                    stack.append(inner)
    assert moved == flops.gn_swish_bytes(shape)


def test_gn_swish_calls_cover_every_resblock():
    """Two fused calls per residual block and one before the output
    convolution; the unconditional pass has the same calls."""
    u = SMALL_UNET
    blocks = (u['n_res_blocks'] * len(u['ch_mults']) + 2
              + (u['n_res_blocks'] + 1) * len(u['ch_mults']))
    calls = flops.gn_swish_calls(u, 3)
    assert len(calls) == 2 * blocks + 1
    assert calls == flops.gn_swish_calls(u, 3, context=False)
    assert all(c[0] == 3 for c in calls)
