"""DeepCache (Ma et al., CVPR 2024) — the paper's strongest *algorithmic*
baseline (Figs. 9-10): cache the deep (low-resolution) UNet features across
adjacent timesteps and recompute only the shallow layers on "skip" steps.

Rationale: in the reverse diffusion trajectory the deep features evolve
slowly; re-running only the outermost level every step recovers most of the
quality at a fraction of the MACs.  We implement the standard interval
variant: a full pass every ``interval`` steps refreshes the cache; skip
steps reuse the cached deepest up-path activation.

This exists (a) as a runnable serving mode (`pipeline_deepcache`) and (b) as
a workload transform for the photonic simulator, so the Fig. 9/10 DeepCache
comparison point can also be *derived* instead of anchored.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.unet import (UNetConfig, attn_block, conv_in, conv_out,
                               downsample, resblock, t_embed, upsample)


def unet_apply_cached(p, cfg: UNetConfig, x: jax.Array, t: jax.Array,
                      cache: Optional[jax.Array], refresh: bool,
                      context=None, policy=None, *, noise_key=None
                      ) -> Tuple[jax.Array, jax.Array]:
    """UNet forward with DeepCache.

    refresh=True  : full pass; returns (eps, new_cache) where the cache is
                    the activation entering the LAST up level.
    refresh=False : recompute only the outermost (full-resolution) down
                    blocks and the last up level, splicing in the cached
                    deep activation.
    Static `refresh` (two jitted variants), matching the interval schedule.
    ``policy`` selects the matmul precision (PrecisionPolicy; the legacy
    positional bool still resolves).
    """
    from repro.core.precision import resolve, stream_for
    pol = resolve(policy)
    keys = stream_for(pol, noise_key)
    g = cfg.groups
    t_emb = t_embed(p, cfg, t)
    h = conv_in(p, x)
    skips = [h]
    # --- outermost down level (always computed) ---
    lvl0 = p['down'][0]
    res = cfg.img_size
    for b in lvl0['blocks']:
        h = resblock(b['res'], h, t_emb, g)
        if 'attn' in b:
            h = attn_block(b['attn'], h, g, cfg.n_heads, context, pol, keys)
        skips.append(h)

    if refresh or cache is None:
        hh = h
        deep_skips = []
        if 'down' in lvl0:
            hh = downsample(lvl0['down'], hh)
            deep_skips.append(hh)
        for lvl_p in p['down'][1:]:
            for b in lvl_p['blocks']:
                hh = resblock(b['res'], hh, t_emb, g)
                if 'attn' in b:
                    hh = attn_block(b['attn'], hh, g, cfg.n_heads, context,
                                    pol, keys)
                deep_skips.append(hh)
            if 'down' in lvl_p:
                hh = downsample(lvl_p['down'], hh)
                deep_skips.append(hh)
        hh = resblock(p['mid']['res1'], hh, t_emb, g)
        hh = attn_block(p['mid']['attn'], hh, g, cfg.n_heads, context, pol, keys)
        hh = resblock(p['mid']['res2'], hh, t_emb, g)
        for lvl_p in p['up'][:-1]:
            for b in lvl_p['blocks']:
                hh = jnp.concatenate([hh, deep_skips.pop()], axis=-1)
                hh = resblock(b['res'], hh, t_emb, g)
                if 'attn' in b:
                    hh = attn_block(b['attn'], hh, g, cfg.n_heads, context,
                                    pol, keys)
            if 'upconv' in lvl_p:
                hh = upsample(lvl_p['upconv'], hh, cfg)
        new_cache = hh                  # activation entering the last level
    else:
        new_cache = cache

    # --- outermost up level (always computed) ---
    h_up = new_cache
    for b in p['up'][-1]['blocks']:
        h_up = jnp.concatenate([h_up, skips.pop()], axis=-1)
        h_up = resblock(b['res'], h_up, t_emb, g)
        if 'attn' in b:
            h_up = attn_block(b['attn'], h_up, g, cfg.n_heads, context,
                              pol, keys)
    return conv_out(p, h_up, g), new_cache


def shallow_workload_fraction(cfg: UNetConfig) -> float:
    """MAC fraction of one *skip* (shallow) pass vs one full UNet pass.

    A skip step recomputes only the outermost down level + last up level
    + in/out convs; we approximate that by the full-resolution share of
    the MAC count.  This single source feeds both the derived DeepCache
    simulator point and the serving engine's photonic accountant, which
    bills skip ticks at this fraction of a full-UNet tick.
    """
    from repro.core.photonic.workload import unet_workload
    full = unet_workload(cfg).total_macs_dense
    shallow_cfg = UNetConfig(
        name=cfg.name + '-shallow', img_size=cfg.img_size, in_ch=cfg.in_ch,
        base_ch=cfg.base_ch, ch_mults=cfg.ch_mults[:1],
        n_res_blocks=cfg.n_res_blocks,
        attn_resolutions=cfg.attn_resolutions, n_heads=cfg.n_heads,
        context_dim=cfg.context_dim)
    return unet_workload(shallow_cfg).total_macs_dense / full


def deepcache_workload_factor(cfg: UNetConfig, interval: int = 5) -> float:
    """Average per-step MAC fraction vs the full UNet (for the simulator's
    derived DeepCache point): 1 full pass + (interval-1) shallow passes."""
    s = shallow_workload_fraction(cfg)
    return (1.0 + (interval - 1) * s) / interval
