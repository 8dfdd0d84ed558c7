"""Operations and bytes of the served work, computed from shapes alone.

FLOPs count the multiply-adds of convolutions, matmuls and attention
(2 per multiply-add) that the algorithm needs: only kernel taps that
meet a real input pixel count, so neither SAME padding nor the zeros a
stride-2 transposed convolution inserts (the served sub-pixel form skips
them) add work.  Normalisation, activations, softmax and the sampler's update are
left out; XLA's ``cost_analysis`` counts them too, which is why the
test holds this count to within a few per cent of XLA's from below.

``gn_swish_bytes`` is the HBM traffic of one fused GroupNorm+swish call
as ``kernels/fused_gn_swish.py`` makes it: the statistics pass reads the
tensor and writes per-tile sums and M2s, and the normalise pass reads
the tensor and a (3, C) coefficient block per sample and writes the
result.
"""
from __future__ import annotations

CONTEXT_TOKENS = 77


def _taps(size_in, k, stride):
    """Kernel taps, summed over one axis's outputs, that meet a real input
    pixel of a SAME-padded convolution (XLA leaves padding out too)."""
    size_out = -(-size_in // stride)
    lo = max((size_out - 1) * stride + k - size_in, 0) // 2
    return sum(0 <= o * stride - lo + d < size_in
               for o in range(size_out) for d in range(k))


def _up_taps(size_in, k=4, stride=2):
    """The same for a stride-2 transposed convolution: taps whose place in
    the zero-inserted input holds a real pixel."""
    lo = k - 1 if stride > k - 1 else -(-(k + stride - 2) // 2)
    count = 0
    for o in range(size_in * stride):
        for d in range(k):
            z = o + d - lo
            count += z % stride == 0 and 0 <= z // stride < size_in
    return count


def _conv(n, h, w, k, c_in, c_out, stride=1):
    """FLOPs of a SAME convolution on an (n, h, w, c_in) input."""
    return 2 * n * _taps(h, k, stride) * _taps(w, k, stride) * c_in * c_out


def _conv_up(n, h, w, c_in, c_out):
    """FLOPs of a 4x4 stride-2 transposed convolution on (n, h, w, c_in)."""
    return 2 * n * _up_taps(h) * _up_taps(w) * c_in * c_out


def _linear(m, d_in, d_out):
    return 2 * m * d_in * d_out


def _res(n, h, w, c_in, c_out, t_dim, ops):
    f = _conv(n, h, w, 3, c_in, c_out) + _conv(n, h, w, 3, c_out, c_out)
    if t_dim is not None:
        f += _linear(n, t_dim, c_out)
    if c_in != c_out:
        f += _conv(n, h, w, 1, c_in, c_out)
    ops.append(('gn_swish', (n, h, w, c_in)))
    ops.append(('gn_swish', (n, h, w, c_out)))
    return f


def _attn(n, res, c, ctx_dim, ops):
    s = res * res
    f = 4 * _linear(n * s, c, c) + 4 * n * s * s * c
    if ctx_dim is not None:
        t = CONTEXT_TOKENS
        f += 2 * _linear(n * s, c, c) + 2 * _linear(n * t, ctx_dim, c)
        f += 4 * n * s * t * c
    ops.append(('gn', (n, res, res, c)))
    return f


def unet_pass(u, batch, context=True, ops=None):
    """FLOPs of one UNet evaluation on ``batch`` rows; ``context=False``
    is the unconditional pass of classifier-free guidance (no
    cross-attention).  ``ops`` collects the normalisation calls."""
    ops = [] if ops is None else ops
    n, b = batch, u['base_ch']
    t_dim = 4 * b
    ctx = u.get('context_dim') if context else None
    res = u['img_size']
    f = _linear(n, b, t_dim) + _linear(n, t_dim, t_dim)
    f += _conv(n, res, res, 3, u['in_ch'], b)
    chs, ch = [b], b
    last = len(u['ch_mults']) - 1
    for lvl, mult in enumerate(u['ch_mults']):
        res = u['img_size'] >> lvl
        for _ in range(u['n_res_blocks']):
            f += _res(n, res, res, ch, b * mult, t_dim, ops)
            ch = b * mult
            if res in u['attn_resolutions']:
                f += _attn(n, res, ch, ctx, ops)
            chs.append(ch)
        if lvl < last:
            f += _conv(n, res, res, 3, ch, ch, stride=2)
            chs.append(ch)
    res = u['img_size'] >> last
    f += _res(n, res, res, ch, ch, t_dim, ops)
    f += _attn(n, res, ch, ctx, ops)
    f += _res(n, res, res, ch, ch, t_dim, ops)
    for lvl in reversed(range(len(u['ch_mults']))):
        res = u['img_size'] >> lvl
        out = b * u['ch_mults'][lvl]
        for _ in range(u['n_res_blocks'] + 1):
            f += _res(n, res, res, ch + chs.pop(), out, t_dim, ops)
            ch = out
            if res in u['attn_resolutions']:
                f += _attn(n, res, ch, ctx, ops)
        if lvl > 0:
            f += _conv_up(n, res, res, ch, ch)
    ops.append(('gn_swish', (n, u['img_size'], u['img_size'], ch)))
    f += _conv(n, u['img_size'], u['img_size'], 3, ch, u['in_ch'])
    return f


def vae_decode(v, batch):
    """FLOPs of decoding ``batch`` latents (the decoder's GroupNorms are
    plain, not the fused kernel)."""
    n, mults = batch, v['ch_mults']
    res = v['img_size'] >> (len(mults) - 1)
    ch = v['base_ch'] * mults[-1]
    f = _conv(n, res, res, 3, v['z_ch'], ch)
    for lvl in reversed(range(len(mults))):
        out = v['base_ch'] * mults[lvl]
        f += _res(n, res, res, ch, out, None, [])
        ch = out
        if lvl > 0:
            f += _conv_up(n, res, res, ch, ch)
            res *= 2
    return f + _conv(n, res, res, 3, ch, v['in_ch'])


def image(cfg, steps: int, guided: bool) -> int:
    """Useful FLOPs of one served image: every step's UNet passes at
    batch 1 (two when guided: conditional and unconditional), plus one
    decode."""
    u = cfg['unet']
    per_step = unet_pass(u, 1)
    if guided:
        per_step += unet_pass(u, 1, context=False)
    f = steps * per_step
    if cfg.get('vae') is not None:
        f += vae_decode(cfg['vae'], 1)
    return f


def gn_swish_calls(u, batch, context=True):
    """Shapes (N, H, W, C) of every fused GroupNorm+swish call in one
    UNet evaluation."""
    ops = []
    unet_pass(u, batch, context, ops)
    return [shape for kind, shape in ops if kind == 'gn_swish']


def _row_tile(h, w, c, tile_bytes=1 << 20):
    th = max(1, min(h, tile_bytes // (w * c * 4)))
    while h % th:
        th -= 1
    return th


def gn_swish_bytes(shape, itemsize=4) -> int:
    """HBM bytes the two ``pallas_call``s of one fused GroupNorm+swish on
    an (N, H, W, C) tensor read and write."""
    n, h, w, c = shape
    tensor = n * h * w * c * itemsize
    tiles = h // _row_tile(h, w, c)
    stats = 2 * n * tiles * c * 4
    coefs = n * 3 * c * 4
    return tensor + stats + (tensor + coefs + tensor)
