"""Jit'd public wrappers around the Pallas kernels.

Handle padding to block multiples, activation quantization, head folding,
and the choice of path: on a TPU backend the wrappers run the Pallas
kernels; on any other backend they run the pure-jnp oracles of
``kernels/ref.py`` (``xla``).  ``REPRO_KERNELS`` forces one mode:
``pallas``, ``xla``, or ``interpret`` (the kernels executed as Python on
the host — what the kernel tests compare against the oracles).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core.quantization import quantize, quantize_per_channel
from repro.kernels import ref as _ref
from repro.kernels.flash_attention import blocks as flash_blocks
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.fused_gn_swish import fused_gn_swish_kernel
from repro.kernels.w8a8_matmul import w8a8_matmul_kernel


def kernel_mode() -> str:
    """'pallas' on TPU, 'xla' elsewhere, or forced via REPRO_KERNELS."""
    forced = os.environ.get('REPRO_KERNELS')
    if forced:
        return forced
    return 'pallas' if jax.default_backend() == 'tpu' else 'xla'


def _pad_to(x: jax.Array, axis: int, multiple: int, value=0):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# W8A8 matmul
# ---------------------------------------------------------------------------

def w8a8_matmul(x: jax.Array, w, *, mode: str | None = None) -> jax.Array:
    """x (..., K) float, w (K, N) float or pre-quantized QTensor
    -> (..., N) f32.

    Quantizes activations per row (dynamic); weights are quantized per
    output channel here unless already a QTensor (serve-time prequant).
    """
    from repro.core.quantization import QTensor
    mode = mode or kernel_mode()
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[-1]
    x2 = x.reshape(-1, K)
    xq = quantize(x2, axis=(1,))
    wq = w if isinstance(w, QTensor) else quantize_per_channel(w)
    if mode == 'xla':
        out = _ref.w8a8_matmul_ref(xq.q, xq.scale, wq.q,
                                   wq.scale.reshape(1, -1))
    else:
        M = x2.shape[0]
        bm = min(128, max(8, M))
        q_p = _pad_to(_pad_to(xq.q, 0, bm), 1, 128)
        s_p = _pad_to(xq.scale, 0, bm)
        wq_p = _pad_to(_pad_to(wq.q, 0, 128), 1, 128)
        ws_p = _pad_to(wq.scale.reshape(1, -1), 1, 128)
        out = w8a8_matmul_kernel(
            q_p, s_p, wq_p, ws_p, bm=bm,
            interpret=(mode == 'interpret'))[:M, :N]
    return out.reshape(*lead, N)


# ---------------------------------------------------------------------------
# Flash attention (streaming LSE softmax)
# ---------------------------------------------------------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, scale: float | None = None,
                    mode: str | None = None) -> jax.Array:
    """q (B, H, S, d), k/v (B, H, T, d) -> (B, H, S, d), float32 from the
    kernel.  The MXU multiplies in q/k/v's own dtype.  The kernel takes the
    heads side by side on the lanes, (B, S, H*d): a caller holding
    (B, S, H, d) and transposing to this signature's layout has both
    transposes cancelled by XLA."""
    from repro.core.lse_softmax import streaming_attention_ref
    mode = mode or kernel_mode()
    B, H, S, d = q.shape
    T = k.shape[2]
    if scale is None:
        scale = d ** -0.5
    if mode == 'xla':
        return streaming_attention_ref(q, k, v, causal=causal, scale=scale)
    qp, kp, vp = (x.transpose(0, 2, 1, 3).reshape(B, x.shape[2], H * d)
                  for x in (q, k, v))
    bb, hg, bq, bk = flash_blocks(B, S, T, H, d, q.dtype.itemsize, causal)
    qp = _pad_to(qp, 1, bq)
    kp = _pad_to(kp, 1, bk)
    vp = _pad_to(vp, 1, bk)
    if kp.shape[1] != T and not causal:
        # ragged T runs the oracle; the UNet never sends it here
        return streaming_attention_ref(q, k, v, causal=False, scale=scale)
    out = flash_attention_kernel(
        qp, kp, vp, heads=H, causal=causal, scale=scale, bb=bb, hg=hg,
        bq=bq, bk=bk, interpret=(mode == 'interpret'))
    return out[:, :S].reshape(B, S, H, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Fused GroupNorm + swish
# ---------------------------------------------------------------------------

def fused_gn_swish(x: jax.Array, scale: jax.Array, bias: jax.Array, *,
                   groups: int = 32, mode: str | None = None) -> jax.Array:
    mode = mode or kernel_mode()
    C = x.shape[-1]
    g = min(groups, C)
    while C % g:
        g -= 1
    if mode == 'xla':
        return _ref.gn_swish_ref(x, scale, bias, groups=g)
    return fused_gn_swish_kernel(x, scale, bias, groups=g,
                                 interpret=(mode == 'interpret'))
