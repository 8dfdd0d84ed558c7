"""Pallas TPU kernel: fused GroupNorm + swish (DiffLight C5).

The paper's Residual unit chains a broadband-MR normalization stage directly
into the SOA swish stage — one optical pass, no intermediate digitization.
The TPU analogue keeps normalize and x*sigmoid(x) in one VMEM pass, so the
normalized activation never round-trips through HBM.

Two ``pallas_call``s over ``(N, H / th)`` row tiles of shape ``(th, W, C)``:

  1. stats: per tile and channel, the sum and the sum of squared
     deviations from the tile's own channel mean;
  2. normalize + swish: ``(x - mu_g) * a_c + bias_c`` then ``y * sigmoid(y)``.

Between them, the tile statistics merge (Chan et al.'s pairwise update) into
per-group mean and variance on a tiny ``(N, tiles, C)`` array.  Every block
keeps the whole channel axis on the lanes: the TPU tiles a block's last two
dims by (8, 128), so a block holding one group's ``C / g`` channels (17 at
``sd_v1_4``'s 340-channel width) would never lower.  ``th`` divides ``H`` and
is the largest row count whose tile fits ``_TILE_BYTES``, which bounds VMEM
at any feature-map size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: f32 bytes one (th, W, C) tile may take; the normalize pass double-buffers
#: an input and an output tile, plus its temporaries, well inside VMEM
_TILE_BYTES = 1 << 20


def _row_tile(H: int, W: int, C: int) -> int:
    row = W * C * 4
    th = max(1, min(H, _TILE_BYTES // row))
    while H % th:
        th -= 1
    return th


def _stats_kernel(x_ref, sum_ref, m2_ref):
    x = x_ref[0].astype(jnp.float32)                    # (th, W, C)
    cnt = x.shape[0] * x.shape[1]
    s = jnp.sum(jnp.sum(x, axis=0), axis=0, keepdims=True)       # (1, C)
    d = x - (s / cnt)[None]
    m2 = jnp.sum(jnp.sum(d * d, axis=0), axis=0, keepdims=True)
    sum_ref[0, 0] = s
    m2_ref[0, 0] = m2


def _norm_kernel(x_ref, coef_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)                    # (th, W, C)
    coef = coef_ref[0]                                  # (3, C): mu, a, bias
    y = (x - coef[0:1][None]) * coef[1:2][None] + coef[2:3][None]
    o_ref[0] = (y * jax.nn.sigmoid(y)).astype(o_ref.dtype)


def _group_coefs(tsum, tm2, scale, bias, groups, cnt, eps):
    """Merge per-(tile, channel) sums and M2s into per-channel
    (mu, a = scale * rsqrt(var + eps), bias) rows: (N, 3, C)."""
    N, T, C = tsum.shape
    cg = C // groups
    mean = (tsum / cnt).reshape(N, T, groups, cg)
    mu = jnp.mean(mean, axis=(1, 3))                    # (N, groups)
    dev = mean - mu[:, None, :, None]
    m2 = (jnp.sum(tm2.reshape(N, T, groups, cg), axis=(1, 3))
          + cnt * jnp.sum(dev * dev, axis=(1, 3)))
    var = m2 / (cnt * T * cg)
    a = scale.reshape(1, groups, cg) * \
        jax.lax.rsqrt(var + eps)[:, :, None]
    mu_c = jnp.broadcast_to(mu[:, :, None], (N, groups, cg))
    return jnp.stack([mu_c.reshape(N, C), a.reshape(N, C),
                      jnp.broadcast_to(bias, (N, C))], axis=1)


@functools.partial(jax.jit, static_argnames=('groups', 'eps', 'interpret'))
def fused_gn_swish_kernel(x: jax.Array, scale: jax.Array, bias: jax.Array, *,
                          groups: int = 32, eps: float = 1e-5,
                          interpret: bool = False) -> jax.Array:
    """x (N, H, W, C), scale/bias (C,).  C % groups == 0."""
    N, H, W, C = x.shape
    assert C % groups == 0, (C, groups)
    th = _row_tile(H, W, C)
    T = H // th
    tile = pl.BlockSpec((1, th, W, C), lambda n, i: (n, i, 0, 0))
    row = pl.BlockSpec((1, 1, 1, C), lambda n, i: (n, i, 0, 0))
    stat = jax.ShapeDtypeStruct((N, T, 1, C), jnp.float32)
    tsum, tm2 = pl.pallas_call(
        _stats_kernel, grid=(N, T), in_specs=[tile],
        out_specs=[row, row], out_shape=[stat, stat],
        interpret=interpret)(x)
    coef = _group_coefs(tsum[:, :, 0], tm2[:, :, 0],
                        scale.astype(jnp.float32), bias.astype(jnp.float32),
                        groups, th * W, eps)
    return pl.pallas_call(
        _norm_kernel,
        grid=(N, T),
        in_specs=[tile, pl.BlockSpec((1, 3, C), lambda n, i: (n, 0, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, coef)
