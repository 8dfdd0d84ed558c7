"""Noise schedules and the forward (noising) process — paper Eq. 1."""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=['betas', 'alphas', 'alpha_bars'],
                   meta_fields=[])
@dataclasses.dataclass(frozen=True)
class Schedule:
    betas: jax.Array            # (T,)
    alphas: jax.Array           # (T,)
    alpha_bars: jax.Array       # (T,) cumulative products

    @property
    def T(self) -> int:
        return self.betas.shape[0]


def linear_schedule(T: int = 1000, beta_0: float = 1e-4,
                    beta_T: float = 0.02) -> Schedule:
    betas = jnp.linspace(beta_0, beta_T, T, dtype=jnp.float32)
    alphas = 1.0 - betas
    return Schedule(betas, alphas, jnp.cumprod(alphas))


def cosine_schedule(T: int = 1000, s: float = 0.008) -> Schedule:
    t = jnp.arange(T + 1, dtype=jnp.float32) / T
    f = jnp.cos((t + s) / (1 + s) * jnp.pi / 2) ** 2
    alpha_bars = f / f[0]
    betas = jnp.clip(1 - alpha_bars[1:] / alpha_bars[:-1], 0, 0.999)
    alphas = 1.0 - betas
    return Schedule(betas, alphas, jnp.cumprod(alphas))


def q_sample(sched: Schedule, x0: jax.Array, t: jax.Array,
             noise: jax.Array) -> jax.Array:
    """Forward process (Eq. 1, closed form over t steps):
    x_t = sqrt(alpha_bar_t) x_0 + sqrt(1 - alpha_bar_t) eps."""
    ab = sched.alpha_bars[t].reshape((-1,) + (1,) * (x0.ndim - 1))
    return jnp.sqrt(ab) * x0 + jnp.sqrt(1.0 - ab) * noise


def ddpm_loss(unet_apply_fn, sched: Schedule, params, x0: jax.Array,
              key: jax.Array, context=None) -> jax.Array:
    """Simple epsilon-prediction objective (Ho et al.)."""
    kt, kn = jax.random.split(key)
    B = x0.shape[0]
    t = jax.random.randint(kt, (B,), 0, sched.T)
    noise = jax.random.normal(kn, x0.shape, x0.dtype)
    x_t = q_sample(sched, x0, t, noise)
    pred = unet_apply_fn(params, x_t, t, context)
    return jnp.mean(jnp.square(pred - noise))
