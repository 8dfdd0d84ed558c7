"""Diffusion UNet (DDPM / LDM / SDM families — paper Table I).

Encoder/decoder ResBlocks with GroupNorm+swish (fused kernel, C5), MHA
blocks with the LSE softmax (C2), optional cross-attention (SDM text
conditioning), and stride-2 transposed-conv upsampling routed through the
sparsity-aware dataflow (C4).  A w8a8 ``PrecisionPolicy`` (see
``repro.core.precision``) runs every attention projection through the
W8A8 path (C1), optionally with analog-noise injection — the serving
configurations the paper evaluates.  The legacy ``quant=True`` flag is a
deprecated alias for ``policy=PrecisionPolicy.w8a8()``.

Every block runs under one ``jax.named_scope`` — ``t_embed``, ``conv_in``,
``resblock``, ``attn`` (self- and cross-attention), ``downsample``,
``upsample``, ``conv_out`` — and scopes never nest, so each compiled op's
``op_name`` metadata names the one block it belongs to.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.attention import NEG_INF
from repro.core.lse_softmax import lse_softmax


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    name: str
    img_size: int
    in_ch: int
    base_ch: int
    ch_mults: Tuple[int, ...]
    n_res_blocks: int
    attn_resolutions: Tuple[int, ...]
    n_heads: int = 8
    context_dim: Optional[int] = None      # cross-attention (SDM)
    transformer_depth: int = 1
    timesteps: int = 1000
    latent: bool = False                    # operates in VAE latent space
    sparse_dataflow: bool = True            # C4 toggle
    groups: int = 32


def timestep_embedding(t: jax.Array, dim: int) -> jax.Array:
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def t_embed(p, cfg: UNetConfig, t: jax.Array) -> jax.Array:
    """The timestep embedding and its MLP."""
    with jax.named_scope('t_embed'):
        t_emb = timestep_embedding(t, cfg.base_ch)
        return L.linear(p['t_mlp2'], L.swish(L.linear(p['t_mlp1'], t_emb)))


def conv_in(p, x: jax.Array) -> jax.Array:
    with jax.named_scope('conv_in'):
        return L.conv2d(p['conv_in'], x)


def downsample(w, h: jax.Array) -> jax.Array:
    with jax.named_scope('downsample'):
        return L.conv2d(w, h, stride=2)


def upsample(w, h: jax.Array, cfg: UNetConfig) -> jax.Array:
    """Stride-2 transposed conv (the C4 sparse-dataflow target)."""
    with jax.named_scope('upsample'):
        return L.conv_transpose2d(w, h, stride=2,
                                  sparse_dataflow=cfg.sparse_dataflow)


def conv_out(p, h: jax.Array, groups: int) -> jax.Array:
    """The output GroupNorm+swish and convolution."""
    with jax.named_scope('conv_out'):
        return L.conv2d(p['conv_out'], _gn_swish(p['gn_out'], h, groups))


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def init_resblock(key, c_in: int, c_out: int, t_dim: int) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p = {
        'gn1': L.init_groupnorm(c_in),
        'conv1': L.init_conv(ks[0], 3, 3, c_in, c_out),
        't_proj': L.init_linear(ks[1], t_dim, c_out),
        'gn2': L.init_groupnorm(c_out),
        'conv2': L.init_conv(ks[2], 3, 3, c_out, c_out),
    }
    if c_in != c_out:
        p['skip'] = L.init_conv(ks[3], 1, 1, c_in, c_out)
    return p


def _gn_swish(gn_p, x, groups):
    from repro.kernels import ops as kops
    return kops.fused_gn_swish(x, gn_p['scale'], gn_p['bias'], groups=groups)


def resblock(p, x: jax.Array, t_emb: jax.Array, groups: int) -> jax.Array:
    with jax.named_scope('resblock'):
        h = _gn_swish(p['gn1'], x, groups)
        h = L.conv2d(p['conv1'], h)
        h = h + L.linear(p['t_proj'], L.swish(t_emb))[:, None, None, :]
        h = _gn_swish(p['gn2'], h, groups)
        h = L.conv2d(p['conv2'], h)
        skip = L.conv2d(p['skip'], x) if 'skip' in p else x
        return skip + h


def init_attn_block(key, ch: int, n_heads: int,
                    context_dim: Optional[int]) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    p = {
        'gn': L.init_groupnorm(ch),
        'wq': L.init_linear(ks[0], ch, ch, bias=False),
        'wk': L.init_linear(ks[1], ch, ch, bias=False),
        'wv': L.init_linear(ks[2], ch, ch, bias=False),
        'wo': L.init_linear(ks[3], ch, ch),
    }
    if context_dim is not None:
        p.update({
            'xq': L.init_linear(ks[4], ch, ch, bias=False),
            'xk': L.init_linear(ks[5], context_dim, ch, bias=False),
            'xv': L.init_linear(ks[6], context_dim, ch, bias=False),
            'xo': L.init_linear(ks[7], ch, ch),
        })
    return p


#: self-attention whose length is a whole number of these 128-lane blocks
#: runs as the flash kernel; the shorter calls' scores are a few MB
FLASH_TOKENS = 128


def _mha(q, k, v, n_heads: int, quant_proj=None) -> jax.Array:
    """q (B, S, C), k/v (B, T, C) -> (B, S, C) via LSE softmax (C2).

    Off the ``xla`` kernel mode, self-attention of a multiple of
    ``FLASH_TOKENS`` tokens, each longer than a head is wide (T > C / h),
    runs as the Pallas flash kernel on bfloat16 q/k/v, so its (B, h, S, S)
    scores never reach HBM.  Every other call is the einsum, whose float32
    operands the TPU's default precision also multiplies as bfloat16; at
    T <= C / h its two passes over the scores cost no more than the
    kernel's float32 output."""
    from repro.kernels import ops as kops
    B, S, C = q.shape
    T = k.shape[1]
    hd = C // n_heads
    if (S == T and T % FLASH_TOKENS == 0 and T > hd
            and kops.kernel_mode() != 'xla'):
        # cast before the head split, so (B, S, C) stays the only layout
        def heads(x):
            return x.astype(jnp.bfloat16).reshape(
                B, S, n_heads, hd).transpose(0, 2, 1, 3)
        o = kops.flash_attention(heads(q.astype(jnp.float32) * hd ** -0.5),
                                 heads(k), heads(v), scale=1.0)
        return o.transpose(0, 2, 1, 3).reshape(B, S, C).astype(q.dtype)
    qh = q.reshape(B, S, n_heads, hd).astype(jnp.float32) * hd ** -0.5
    kh = k.reshape(B, T, n_heads, hd).astype(jnp.float32)
    vh = v.reshape(B, T, n_heads, hd).astype(jnp.float32)
    s = jnp.einsum('bshd,bthd->bhst', qh, kh)
    pr = lse_softmax(s, axis=-1)
    o = jnp.einsum('bhst,bthd->bshd', pr, vh)
    return o.reshape(B, S, C).astype(q.dtype)


def attn_block(p, x: jax.Array, groups: int, n_heads: int,
               context: Optional[jax.Array] = None,
               policy=None, keys=None) -> jax.Array:
    """``policy`` selects the matmul precision for every projection (a
    PrecisionPolicy; the legacy positional bool still resolves).  ``keys``
    is a NoiseKeyStream dispensing one key per projection when the policy
    injects analog noise — without one, a per-block stream anchored at the
    policy's seed is used."""
    from repro.core.precision import resolve, stream_for
    pol = resolve(policy)
    if keys is None:
        keys = stream_for(pol)
    B, H, W, C = x.shape

    def proj(q, v):
        return L.linear(q, v, policy=pol, noise_key=keys.next())

    with jax.named_scope('attn'):
        h = L.groupnorm(p['gn'], x, groups)
        t = h.reshape(B, H * W, C)
        o = _mha(proj(p['wq'], t), proj(p['wk'], t), proj(p['wv'], t),
                 n_heads)
        t = t + proj(p['wo'], o)
        if context is not None and 'xq' in p:
            o = _mha(proj(p['xq'], t), proj(p['xk'], context),
                     proj(p['xv'], context), n_heads)
            t = t + proj(p['xo'], o)
        return x + t.reshape(B, H, W, C)


# ---------------------------------------------------------------------------
# UNet
# ---------------------------------------------------------------------------

def init_unet(key, cfg: UNetConfig) -> Dict[str, Any]:
    t_dim = cfg.base_ch * 4
    it = iter(jax.random.split(key, 1024))
    p: Dict[str, Any] = {
        't_mlp1': L.init_linear(next(it), cfg.base_ch, t_dim),
        't_mlp2': L.init_linear(next(it), t_dim, t_dim),
        'conv_in': L.init_conv(next(it), 3, 3, cfg.in_ch, cfg.base_ch),
    }
    chs = [cfg.base_ch]
    ch = cfg.base_ch
    res = cfg.img_size
    down = []
    for lvl, mult in enumerate(cfg.ch_mults):
        out_ch = cfg.base_ch * mult
        blocks = []
        for _ in range(cfg.n_res_blocks):
            b = {'res': init_resblock(next(it), ch, out_ch, t_dim)}
            ch = out_ch
            if res in cfg.attn_resolutions:
                b['attn'] = init_attn_block(next(it), ch, cfg.n_heads,
                                            cfg.context_dim)
            blocks.append(b)
            chs.append(ch)
        lvl_p = {'blocks': blocks}
        if lvl < len(cfg.ch_mults) - 1:
            lvl_p['down'] = L.init_conv(next(it), 3, 3, ch, ch)
            chs.append(ch)
            res //= 2
        down.append(lvl_p)
    p['down'] = down
    p['mid'] = {
        'res1': init_resblock(next(it), ch, ch, t_dim),
        'attn': init_attn_block(next(it), ch, cfg.n_heads, cfg.context_dim),
        'res2': init_resblock(next(it), ch, ch, t_dim),
    }
    up = []
    for lvl, mult in reversed(list(enumerate(cfg.ch_mults))):
        out_ch = cfg.base_ch * mult
        blocks = []
        for _ in range(cfg.n_res_blocks + 1):
            b = {'res': init_resblock(next(it), ch + chs.pop(), out_ch,
                                      t_dim)}
            ch = out_ch
            if res in cfg.attn_resolutions:
                b['attn'] = init_attn_block(next(it), ch, cfg.n_heads,
                                            cfg.context_dim)
            blocks.append(b)
        lvl_p = {'blocks': blocks}
        if lvl > 0:
            # stride-2 transposed conv -> C4 sparse dataflow target
            lvl_p['upconv'] = L.init_conv(next(it), 4, 4, ch, ch)
            res *= 2
        up.append(lvl_p)
    p['up'] = up
    p['gn_out'] = L.init_groupnorm(ch)
    p['conv_out'] = L.init_conv(next(it), 3, 3, ch, cfg.in_ch)
    return p


def unet_apply(p, cfg: UNetConfig, x: jax.Array, t: jax.Array,
               context: Optional[jax.Array] = None,
               quant: bool = False, *, policy=None,
               noise_key=None) -> jax.Array:
    """x (B, H, W, C_in), t (B,) int timesteps -> predicted noise.

    ``policy`` is the PrecisionPolicy for every attention projection
    (fp32 / w8a8 / w8a8+noise); ``quant=True`` is its deprecated boolean
    ancestor.  A noisy policy draws one independent perturbation per
    projection from ``noise_key`` (default: the policy's seed anchor),
    so the whole forward is deterministic under a fixed key.
    """
    from repro.core.precision import resolve, stream_for
    pol = resolve(policy, quant)
    keys = stream_for(pol, noise_key)
    g = cfg.groups
    t_emb = t_embed(p, cfg, t)
    h = conv_in(p, x)
    skips = [h]
    for lvl, lvl_p in enumerate(p['down']):
        for b in lvl_p['blocks']:
            h = resblock(b['res'], h, t_emb, g)
            if 'attn' in b:
                h = attn_block(b['attn'], h, g, cfg.n_heads, context, pol, keys)
            skips.append(h)
        if 'down' in lvl_p:
            h = downsample(lvl_p['down'], h)
            skips.append(h)
    h = resblock(p['mid']['res1'], h, t_emb, g)
    h = attn_block(p['mid']['attn'], h, g, cfg.n_heads, context, pol, keys)
    h = resblock(p['mid']['res2'], h, t_emb, g)
    for lvl_p in p['up']:
        for b in lvl_p['blocks']:
            h = jnp.concatenate([h, skips.pop()], axis=-1)
            h = resblock(b['res'], h, t_emb, g)
            if 'attn' in b:
                h = attn_block(b['attn'], h, g, cfg.n_heads, context, pol, keys)
        if 'upconv' in lvl_p:
            h = upsample(lvl_p['upconv'], h, cfg)
    return conv_out(p, h, g)
