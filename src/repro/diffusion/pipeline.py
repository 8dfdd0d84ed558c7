"""End-to-end diffusion serving pipeline (the paper's workload).

Batched request generation: noise -> iterative UNet denoising (DDPM or DDIM)
-> (for latent models) VAE decode.  The pipeline carries a
``PrecisionPolicy`` (``repro.core.precision``) selecting how UNet matmuls
execute — fp32, the W8A8 photonic path (C1), or W8A8 with analog-noise
injection — and every apply entry point takes a per-call ``policy=``
override so one pipeline can serve requests at different precisions (the
serving engine's per-request precision selection).  The legacy
``quant: bool`` is a deprecated alias for ``policy=PrecisionPolicy.w8a8()``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.precision import PrecisionPolicy, resolve
from repro.diffusion import samplers
from repro.diffusion.schedule import Schedule, linear_schedule
from repro.models import autoencoder as AE
from repro.models import unet as U


@dataclasses.dataclass
class DiffusionPipeline:
    unet_cfg: U.UNetConfig
    unet_params: Any
    sched: Schedule
    vae_cfg: Optional[AE.VAEConfig] = None
    vae_params: Any = None
    policy: PrecisionPolicy = PrecisionPolicy.fp32()

    def __post_init__(self):
        # one-release shim: a bool / name in the policy slot still resolves
        if not isinstance(self.policy, PrecisionPolicy):
            self.policy = resolve(self.policy)

    @classmethod
    def init(cls, key, unet_cfg: U.UNetConfig,
             vae_cfg: Optional[AE.VAEConfig] = None,
             timesteps: Optional[int] = None, quant: Optional[bool] = None,
             policy: Optional[PrecisionPolicy] = None):
        """Build a pipeline with freshly initialized params.  ``policy``
        sets the default execution precision; ``quant=True`` is the
        deprecated boolean form of ``policy=PrecisionPolicy.w8a8()``."""
        k1, k2 = jax.random.split(key)
        unet_params = U.init_unet(k1, unet_cfg)
        vae_params = AE.init_vae(k2, vae_cfg) if vae_cfg else None
        sched = linear_schedule(timesteps or unet_cfg.timesteps)
        return cls(unet_cfg, unet_params, sched, vae_cfg, vae_params,
                   resolve(policy, quant))

    @property
    def quant(self) -> bool:
        """Deprecated view of the default policy (kept for one release)."""
        return self.policy.quantized

    def prequantize(self) -> 'DiffusionPipeline':
        """Serve-time calibration: pre-quantize every attention projection
        weight to a per-output-channel QTensor — exactly the weights the
        dynamic w8a8 path quantizes on the fly, with the same scale rule,
        so outputs agree to rounding (~1 LSB at tie boundaries) — and pin
        the policy's calibration mode."""
        from repro.core.quantization import quantize_per_channel
        proj = {'wq', 'wk', 'wv', 'wo', 'xq', 'xk', 'xv', 'xo'}

        def one(path, leaf):
            names = [str(getattr(k, 'key', '')) for k in path]
            if len(names) >= 2 and names[-1] == 'w' and names[-2] in proj:
                return quantize_per_channel(leaf)
            return leaf
        params = jax.tree_util.tree_map_with_path(one, self.unet_params)
        pol = self.policy if self.policy.quantized else PrecisionPolicy.w8a8()
        return dataclasses.replace(
            self, unet_params=params,
            policy=dataclasses.replace(pol, calibration='prequant'))

    def generate_deepcache(self, key, batch: int, steps: int = 50,
                           interval: int = 5, context=None,
                           policy: Optional[PrecisionPolicy] = None
                           ) -> jax.Array:
        """DDIM sampling with the DeepCache baseline ([21]): a full UNet
        pass every `interval` steps, shallow passes in between (deep
        features reused).  Python-level step loop (two jitted variants).
        With ``interval=1`` every step refreshes, so the output matches
        ``generate`` exactly.  ``policy`` overrides the pipeline's
        default precision for this call (the serving engine's cached
        fast path runs the same ``unet_apply_cached`` under per-request
        policies)."""
        from repro.diffusion.deepcache import unet_apply_cached
        import jax as _jax
        pol = resolve(policy) if policy is not None else self.policy
        sched = self.sched
        ts = samplers.ddim_timesteps(sched, steps)
        shape = self.sample_shape(batch)
        k0, key = jax.random.split(key)
        x = jax.random.normal(k0, shape)
        cache = None
        full = _jax.jit(lambda p, xx, tt, ctx: unet_apply_cached(
            p, self.unet_cfg, xx, tt, None, True, ctx, pol))
        shallow = _jax.jit(lambda p, xx, tt, c, ctx: unet_apply_cached(
            p, self.unet_cfg, xx, tt, c, False, ctx, pol))
        for i, t in enumerate(ts):
            tb = jnp.full((batch,), int(t), jnp.int32)
            if i % interval == 0 or cache is None:
                eps, cache = full(self.unet_params, x, tb, context)
            else:
                eps, _ = shallow(self.unet_params, x, tb, cache, context)
            t_prev = int(ts[i + 1]) if i + 1 < steps else -1
            x = samplers.ddim_step(sched, eps, x, int(t), t_prev)
        if self.vae_params is not None:
            x = AE.vae_decode(self.vae_params, self.vae_cfg, x)
        return x

    def sample_shape(self, batch: int):
        c = self.unet_cfg
        return (batch, c.img_size, c.img_size, c.in_ch)

    def denoise_step(self, x: jax.Array, t: jax.Array, t_prev: jax.Array,
                     context=None, guidance: float = 0.0,
                     policy: Optional[PrecisionPolicy] = None,
                     noise_key=None) -> jax.Array:
        """One mixed-timestep DDIM step: `t` / `t_prev` are per-sample
        (B,) vectors, so a batch may hold samples at different denoising
        depths (the serving engine's per-tick kernel).  ``policy``
        overrides the pipeline default for this step."""
        pol = resolve(policy) if policy is not None else self.policy
        eps = eps_fn(self.unet_cfg, self.unet_params, context, guidance,
                     pol, noise_key)(x, jnp.asarray(t, jnp.int32))
        return samplers.ddim_step(self.sched, eps, x, t, t_prev)

    def generate(self, key, batch: int, steps: int = 50,
                 sampler: str = 'ddim', context=None,
                 guidance: float = 0.0,
                 policy: Optional[PrecisionPolicy] = None) -> jax.Array:
        """Serve one batch of generation requests; returns images/latents.
        ``policy`` overrides the pipeline's default precision.  One jitted
        program per (shapes, steps, sampler, guidance, policy) that takes
        the UNet and VAE weights as arguments."""
        pol = resolve(policy) if policy is not None else self.policy
        return _generate(self.unet_params, self.vae_params, self.sched, key,
                         context, unet_cfg=self.unet_cfg,
                         vae_cfg=self.vae_cfg, batch=batch, steps=steps,
                         sampler=sampler, guidance=float(guidance),
                         policy=pol)


def eps_fn(cfg: U.UNetConfig, params, context=None, guidance: float = 0.0,
           policy: PrecisionPolicy = PrecisionPolicy.fp32(), noise_key=None):
    """Noise-prediction closure ``eps(x, t)`` over explicit UNet
    ``params``.  For a noisy policy the per-evaluation key folds in the
    (first) timestep so the analog draw varies along the trajectory; an
    explicit ``noise_key`` re-anchors it (the engine threads a per-tick
    key).  ``guidance > 0`` with a ``context`` blends in the
    unconditional prediction (classifier-free guidance)."""
    base = None
    if policy.noisy:
        base = noise_key if noise_key is not None else \
            jax.random.PRNGKey(policy.noise_seed)

    def keyed(t, branch):
        if base is None:
            return None
        k = jax.random.fold_in(base, jnp.reshape(t, (-1,))[0])
        return jax.random.fold_in(k, branch)

    def eps(x, t):
        e = U.unet_apply(params, cfg, x, t, context=context, policy=policy,
                         noise_key=keyed(t, 0))
        if guidance > 0.0 and context is not None:
            e_unc = U.unet_apply(params, cfg, x, t, context=None,
                                 policy=policy, noise_key=keyed(t, 1))
            e = e_unc + guidance * (e - e_unc)
        return e
    return eps


@functools.partial(jax.jit, static_argnames=(
    'unet_cfg', 'vae_cfg', 'batch', 'steps', 'sampler', 'guidance',
    'policy'))
def _generate(unet_params, vae_params, sched: Schedule, key, context, *,
              unet_cfg, vae_cfg, batch, steps, sampler, guidance, policy):
    eps = eps_fn(unet_cfg, unet_params, context, guidance, policy)
    shape = (batch, unet_cfg.img_size, unet_cfg.img_size, unet_cfg.in_ch)
    if sampler == 'ddpm':
        z = samplers.ddpm_sample(sched, eps, shape, key)
    else:
        z = samplers.ddim_sample(sched, eps, shape, key, steps=steps)
    if vae_params is not None:
        z = AE.vae_decode(vae_params, vae_cfg, z)
    return z
