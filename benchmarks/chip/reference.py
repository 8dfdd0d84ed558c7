"""Plain reference of the served diffusion models, written from the
configuration file alone: the UNet, the VAE decoder, the linear noise
schedule, DDIM (eta = 0) and classifier-free guidance, in straightforward
``jax.numpy``.  It imports nothing of the system under test.

Everything is float32, and every convolution and matmul runs at
``Precision.HIGHEST``: the reference.  ``operands='int8'`` (or
``'fp8'``) rounds both operands of every convolution and matmul to that
type first, each with a symmetric scale of its own (weights per output
channel, activations per row of the contraction, as the program's w8a8
rule scales them), and keeps everything else in float32: the control,
one step below the bfloat16 operands at which the configuration's
float32 matmuls run on a TPU.

The parameter layout (``unet_shapes`` / ``vae_decoder_shapes``) is the
nested-dict layout the served UNet reads; ``weights.py`` fills it from
the seed, and this module reads the same arrays by name.

**The model interface.**  A configuration file names its model module in
``"model"`` (a module of this directory; ``models.of`` looks it up), and
the benchmark reaches the model through nothing else.  This module is the
one for the repo's UNet block; a module for another architecture has the
same names:

- ``shapes(cfg)``: ``{'unet': tree, 'vae': tree or None}``, nested dicts
  whose leaves are shape tuples; ``weights.py`` draws each leaf by its
  key (``w``, ``b``, ``scale``, anything else).
- ``sample(unet_p, vae_p, cfg, seed, steps, guidance, context,
  operands=None)``: the image one request should come out as, a NumPy
  array; ``operands`` names a control rounding of ``OPERANDS``.  Another
  module passes its own UNet, decoder and alpha-bars to ``ddim_sample``,
  the loop this one uses.
- ``context_rows(seed, tokens, dim)``: the conditioning every slot is
  served with.
- ``OPERANDS``: the control's operand roundings, by name.
- ``image_flops(cfg, steps, guided)``: useful FLOPs of one served image.
- ``gn_swish_calls(cfg, batch, context=True)``: the (N, H, W, C) shape
  of every fused GroupNorm+swish call in one UNet evaluation.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import flops

F32 = jnp.float32


# ---------------------------------------------------------------------------
# parameter layout
# ---------------------------------------------------------------------------

def _lin(d_in, d_out, bias=True):
    p = {'w': (d_in, d_out)}
    if bias:
        p['b'] = (d_out,)
    return p


def _conv(k, c_in, c_out):
    return {'w': (k, k, c_in, c_out), 'b': (c_out,)}


def _gn(c):
    return {'scale': (c,), 'bias': (c,)}


def _res_shapes(c_in, c_out, t_dim=None):
    p = {'gn1': _gn(c_in), 'conv1': _conv(3, c_in, c_out),
         'gn2': _gn(c_out), 'conv2': _conv(3, c_out, c_out)}
    if t_dim is not None:
        p['t_proj'] = _lin(t_dim, c_out)
    if c_in != c_out:
        p['skip'] = _conv(1, c_in, c_out)
    return p


def _attn_shapes(ch, context_dim):
    p = {'gn': _gn(ch), 'wq': _lin(ch, ch, False), 'wk': _lin(ch, ch, False),
         'wv': _lin(ch, ch, False), 'wo': _lin(ch, ch)}
    if context_dim is not None:
        p.update({'xq': _lin(ch, ch, False),
                  'xk': _lin(context_dim, ch, False),
                  'xv': _lin(context_dim, ch, False), 'xo': _lin(ch, ch)})
    return p


def _levels(u):
    """(level, out_ch, resolution) of each UNet level, top down."""
    return [(lvl, u['base_ch'] * m, u['img_size'] >> lvl)
            for lvl, m in enumerate(u['ch_mults'])]


def unet_shapes(u):
    """Nested dict of parameter shapes (tuples) for the UNet config ``u``."""
    b, t_dim, ctx = u['base_ch'], u['base_ch'] * 4, u.get('context_dim')
    p = {'t_mlp1': _lin(b, t_dim), 't_mlp2': _lin(t_dim, t_dim),
         'conv_in': _conv(3, u['in_ch'], b)}
    chs, ch, down = [b], b, []
    last = len(u['ch_mults']) - 1
    for lvl, out_ch, res in _levels(u):
        blocks = []
        for _ in range(u['n_res_blocks']):
            blk = {'res': _res_shapes(ch, out_ch, t_dim)}
            ch = out_ch
            if res in u['attn_resolutions']:
                blk['attn'] = _attn_shapes(ch, ctx)
            blocks.append(blk)
            chs.append(ch)
        level = {'blocks': blocks}
        if lvl < last:
            level['down'] = _conv(3, ch, ch)
            chs.append(ch)
        down.append(level)
    p['down'] = down
    p['mid'] = {'res1': _res_shapes(ch, ch, t_dim),
                'attn': _attn_shapes(ch, ctx),
                'res2': _res_shapes(ch, ch, t_dim)}
    up = []
    for lvl, out_ch, res in reversed(_levels(u)):
        blocks = []
        for _ in range(u['n_res_blocks'] + 1):
            blk = {'res': _res_shapes(ch + chs.pop(), out_ch, t_dim)}
            ch = out_ch
            if res in u['attn_resolutions']:
                blk['attn'] = _attn_shapes(ch, ctx)
            blocks.append(blk)
        level = {'blocks': blocks}
        if lvl > 0:
            level['upconv'] = _conv(4, ch, ch)
        up.append(level)
    p['up'] = up
    p['gn_out'] = _gn(ch)
    p['conv_out'] = _conv(3, ch, u['in_ch'])
    return p


def vae_decoder_shapes(v):
    """Parameter shapes of the VAE decoder (the only part that serves):
    one residual block per level and no mid-block, as served."""
    if v.get('res_blocks_per_level', 1) != 1 or v.get('mid_block', False):
        raise ValueError('the served decoder has one residual block per '
                         'level and no mid-block')
    mults = v['ch_mults']
    ch = v['base_ch'] * mults[-1]
    p = {'dec_in': _conv(3, v['z_ch'], ch)}
    dec = []
    for lvl in reversed(range(len(mults))):
        out = v['base_ch'] * mults[lvl]
        level = {'res': _res_shapes(ch, out)}
        ch = out
        if lvl > 0:
            level['up'] = _conv(4, ch, ch)
        dec.append(level)
    p['dec'] = dec
    p['dec_gn'] = _gn(ch)
    p['dec_out'] = _conv(3, ch, v['in_ch'])
    return p


def shapes(cfg):
    """{'unet': ..., 'vae': ...} parameter shape trees; 'vae' is None
    without a VAE."""
    vae = cfg.get('vae')
    return {'unet': unet_shapes(cfg['unet']),
            'vae': None if vae is None else vae_decoder_shapes(vae)}


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

HIGHEST = jax.lax.Precision.HIGHEST


def _int8(x, axes):
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _fp8(x, axes):
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


#: operand roundings of the control, by name
OPERANDS = {'int8': _int8, 'fp8': _fp8}

_round = None       # the control's operand rounding while it traces


@contextlib.contextmanager
def _operands(name):
    global _round
    _round = None if name is None else OPERANDS[name]
    try:
        yield
    finally:
        _round = None


def _q(x, axes):
    """``x`` as the control rounds it (scales shared along ``axes``)."""
    return x if _round is None else _round(x, axes)


def _groups(c, groups):
    """The largest group count <= ``groups`` that divides ``c``."""
    g = min(groups, c)
    while c % g:
        g -= 1
    return g


def group_norm(p, x, groups, eps=1e-5):
    n, h, w, c = x.shape
    g = _groups(c, groups)
    xg = x.reshape(n, h, w, g, c // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    y = ((xg - mu) / jnp.sqrt(var + eps)).reshape(n, h, w, c)
    return y * p['scale'] + p['bias']


def swish(x):
    return x * jax.nn.sigmoid(x)


def conv(p, x, stride=1):
    y = jax.lax.conv_general_dilated(
        _q(x, -1), _q(p['w'], (0, 1, 2)), (stride, stride), 'SAME',
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'), precision=HIGHEST)
    return y + p['b']


def conv_up(p, x):
    """Stride-2 transposed convolution, SAME padding (the dense form)."""
    y = jax.lax.conv_transpose(
        _q(x, -1), _q(p['w'], (0, 1, 2)), (2, 2), 'SAME',
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'), precision=HIGHEST)
    return y + p['b']


def linear(p, x):
    y = jnp.einsum('...i,io->...o', _q(x, -1), _q(p['w'], 0),
                   precision=HIGHEST)
    return y + p['b'] if 'b' in p else y


def attention(q, k, v, heads):
    b, s, c = q.shape
    hd = c // heads
    q = q.reshape(b, s, heads, hd) * (hd ** -0.5)
    k = k.reshape(b, k.shape[1], heads, hd)
    v = v.reshape(b, v.shape[1], heads, hd)
    w = jax.nn.softmax(jnp.einsum('bshd,bthd->bhst', _q(q, -1), _q(k, -1),
                                  precision=HIGHEST), axis=-1)
    return jnp.einsum('bhst,bthd->bshd', _q(w, -1), _q(v, 1),
                      precision=HIGHEST).reshape(b, s, c)


def res_block(p, x, t_emb, groups):
    h = conv(p['conv1'], swish(group_norm(p['gn1'], x, groups)))
    if t_emb is not None:
        h = h + linear(p['t_proj'], swish(t_emb))[:, None, None, :]
    h = conv(p['conv2'], swish(group_norm(p['gn2'], h, groups)))
    return (conv(p['skip'], x) if 'skip' in p else x) + h


def attn_block(p, x, groups, heads, context):
    """Self-attention, then cross-attention over ``context`` when given.
    The block's residual stream starts from the normalised input, and the
    block's input is added back at the end."""
    b, h, w, c = x.shape
    t = group_norm(p['gn'], x, groups).reshape(b, h * w, c)
    t = t + linear(p['wo'], attention(linear(p['wq'], t), linear(p['wk'], t),
                                      linear(p['wv'], t), heads))
    if context is not None:
        t = t + linear(p['xo'], attention(
            linear(p['xq'], t), linear(p['xk'], context),
            linear(p['xv'], context), heads))
    return x + t.reshape(b, h, w, c)


def timestep_embedding(t, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=F32) / half)
    ang = t.astype(F32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def unet(p, u, x, t, context=None):
    """Predicted noise for latents ``x`` (B, H, W, C) at timesteps ``t``."""
    g, heads = u['groups'], u['n_heads']
    emb = timestep_embedding(t, u['base_ch'])
    emb = linear(p['t_mlp2'], swish(linear(p['t_mlp1'], emb)))
    h = conv(p['conv_in'], x)
    skips = [h]
    for level in p['down']:
        for blk in level['blocks']:
            h = res_block(blk['res'], h, emb, g)
            if 'attn' in blk:
                h = attn_block(blk['attn'], h, g, heads, context)
            skips.append(h)
        if 'down' in level:
            h = conv(level['down'], h, stride=2)
            skips.append(h)
    h = res_block(p['mid']['res1'], h, emb, g)
    h = attn_block(p['mid']['attn'], h, g, heads, context)
    h = res_block(p['mid']['res2'], h, emb, g)
    for level in p['up']:
        for blk in level['blocks']:
            h = res_block(blk['res'], jnp.concatenate([h, skips.pop()], -1),
                          emb, g)
            if 'attn' in blk:
                h = attn_block(blk['attn'], h, g, heads, context)
        if 'upconv' in level:
            h = conv_up(level['upconv'], h)
    return conv(p['conv_out'], swish(group_norm(p['gn_out'], h, g)))


def vae_decode(p, v, z):
    g = v['groups']
    h = conv(p['dec_in'], z)
    for level in p['dec']:
        h = res_block(level['res'], h, None, g)
        if 'up' in level:
            h = conv_up(level['up'], h)
    return jnp.tanh(conv(p['dec_out'], swish(group_norm(p['dec_gn'], h, g))))


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def alpha_bars(T, beta_0, beta_T):
    """Cumulative products of the linear schedule, in float64."""
    return np.cumprod(1.0 - np.linspace(beta_0, beta_T, T, dtype=np.float64))


def linear_alpha_bars(cfg):
    """``alpha_bars`` of the configuration's ``schedule``, which has to be
    ``linear``."""
    s = cfg['schedule']
    if s['kind'] != 'linear':
        raise ValueError(f"the reference has no {s['kind']!r} schedule, "
                         "only 'linear'")
    return alpha_bars(cfg['unet']['timesteps'], s['beta_0'], s['beta_T'])


def ddim_timesteps(T, steps):
    """The DDIM sub-sequence T-1 ... 0 the configuration serves (uniform,
    truncated to integers)."""
    return np.linspace(T - 1, 0, steps).astype(np.int32)


def initial_noise(seed, shape):
    """A request's starting latents: normal draws from the first half of
    ``PRNGKey(seed)`` split in two."""
    return jax.random.normal(jax.random.split(jax.random.PRNGKey(seed))[0],
                             shape, F32)


@functools.partial(jax.jit,
                   static_argnames=('unet_fn', 'u', 'guided', 'operands'))
def _ddim_step(p, x, t, c, context, guidance, *, unet_fn, u, guided,
               operands):
    """One DDIM update; ``c`` = (sqrt(ab_t), sqrt(1-ab_t), sqrt(ab_prev),
    sqrt(1-ab_prev))."""
    tb = jnp.reshape(t, (1,))
    with _operands(operands):
        eps = unet_fn(p, u, x, tb, context)
        if guided:
            e_unc = unet_fn(p, u, x, tb, None)
            eps = e_unc + guidance * (eps - e_unc)
    x0 = (x - c[1] * eps) / c[0]
    return c[2] * x0 + c[3] * eps


@functools.partial(jax.jit, static_argnames=('decode_fn', 'v', 'operands'))
def _decode(p, z, *, decode_fn, v, operands):
    with _operands(operands):
        return decode_fn(p, v, z)


def _hashable(cfg):
    """A config dict as a hashable static argument."""
    return _Frozen(cfg)


class _Frozen(dict):
    def __hash__(self):
        return hash(repr(sorted(self.items())))

    def __eq__(self, other):
        return isinstance(other, dict) and dict.__eq__(self, other)


def ddim_sample(unet_fn, decode_fn, ab, unet_p, vae_p, cfg, seed, steps,
                guidance, context, operands=None):
    """``sample`` for any model: DDIM over the alpha-bars ``ab`` with
    ``unet_fn(p, u, x, t, context)`` as the noise prediction and
    ``decode_fn(p, v, z)`` as the decoder (used when the configuration
    has a VAE).  Both are traced with the control's rounding in force, so
    a model built from this module's layers gets its control for free."""
    u = _hashable(cfg['unet'])
    T = u['timesteps']
    ts = ddim_timesteps(T, steps)
    shape = (1, u['img_size'], u['img_size'], u['in_ch'])
    x = initial_noise(seed, shape)
    guided = bool(guidance > 0 and context is not None)
    g = jnp.asarray(guidance, F32)
    for i, t in enumerate(ts):
        ab_prev = ab[ts[i + 1]] if i + 1 < len(ts) else 1.0
        coefs = jnp.asarray([math.sqrt(ab[t]), math.sqrt(1 - ab[t]),
                             math.sqrt(ab_prev), math.sqrt(1 - ab_prev)], F32)
        x = _ddim_step(unet_p, x, jnp.int32(t), coefs, context, g,
                       unet_fn=unet_fn, u=u, guided=guided,
                       operands=operands)
    if cfg.get('vae') is not None:
        x = _decode(vae_p, x, decode_fn=decode_fn,
                    v=_hashable(cfg['vae']), operands=operands)
    return np.asarray(x[0])


def sample(unet_p, vae_p, cfg, seed, steps, guidance, context,
           operands=None):
    """The image one request should come out as: DDIM from the request's
    noise over ``steps`` steps, guided when ``guidance > 0`` and a context
    is given, decoded when the configuration has a VAE.  ``operands``
    names the control's operand rounding (``OPERANDS``); None is the
    reference."""
    return ddim_sample(unet, vae_decode, linear_alpha_bars(cfg), unet_p,
                       vae_p, cfg, seed, steps, guidance, context, operands)


def context_rows(seed, tokens, dim):
    """The conditioning every slot is served with: one seeded
    ``(1, tokens, dim)`` normal draw from ``PRNGKey(seed + 1)``."""
    return jax.random.normal(jax.random.PRNGKey(seed + 1), (1, tokens, dim),
                             F32)


def image_flops(cfg, steps, guided):
    return flops.image(cfg, steps, guided)


def gn_swish_calls(cfg, batch, context=True):
    return flops.gn_swish_calls(cfg['unet'], batch, context)
