"""Seeded weights for a configuration, in the layout its model module
gives (``shapes``), drawn on the device in one compiled program, in
float32 (the type they are served in).

The draws use the ``rbg`` generator (XLA's RngBitGenerator), one
operation per leaf, which compiles much faster than threefry's unrolled
rounds.  Every matrix and kernel ``w`` is uniform in +-1/sqrt(fan_in)
(fan_in is the product of all but the output axis), biases ``b`` are
N(0, 0.02^2), GroupNorm scales 1 + N(0, 0.1^2) and GroupNorm biases
N(0, 0.1^2), so that no affine term of the model is an identity the
comparison could not see.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import models


def _draw(key, name, shape):
    if name == 'w':
        lim = 1.0 / math.sqrt(math.prod(shape[:-1]))
        return jax.random.uniform(key, shape, jnp.float32, -lim, lim)
    if name == 'scale':
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    std = 0.02 if name == 'b' else 0.1
    return std * jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw_all(key, names, dims):
    keys = jax.random.split(key, len(dims))
    return [_draw(k, n, s) for k, n, s in zip(keys, names, dims)]


def _is_shape(x):
    return isinstance(x, tuple)


def shapes(cfg):
    """{'unet': ..., 'vae': ...} parameter shape trees of the
    configuration's model; 'vae' is None without a VAE."""
    return models.of(cfg).shapes(cfg)


def make(cfg, seed: int):
    """{'unet': params, 'vae': params or None} for ``cfg``, drawn from
    ``seed``."""
    tree = shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=_is_shape)
    names = tuple(str(path[-1].key) for path, _ in leaves)
    dims = tuple(s for _, s in leaves)
    arrays = _draw_all(jax.random.key(seed, impl='rbg'), names, dims)
    return jax.tree_util.tree_unflatten(treedef, arrays)


def count(tree) -> int:
    return sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        tree, is_leaf=_is_shape))
