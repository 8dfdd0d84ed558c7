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
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.fused_gn_swish import fused_gn_swish_kernel
from repro.kernels.w8a8_matmul import w8a8_matmul_kernel


def _mode() -> str:
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
    mode = mode or _mode()
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
    """q (B, H, S, d), k/v (B, H, T, d) -> (B, H, S, d)."""
    mode = mode or _mode()
    B, H, S, d = q.shape
    T = k.shape[2]
    if scale is None:
        scale = d ** -0.5
    if mode == 'xla':
        from repro.core.lse_softmax import streaming_attention_ref
        return streaming_attention_ref(q, k, v, causal=causal, scale=scale)
    qf = q.reshape(B * H, S, d)
    kf = k.reshape(B * H, T, d)
    vf = v.reshape(B * H, T, d)
    bq = min(128, S)
    bk = min(128, T)
    q_p = _pad_to(qf, 1, bq)
    k_p = _pad_to(kf, 1, bk)
    v_p = _pad_to(vf, 1, bk)
    if k_p.shape[1] != T:
        # padded KV rows must not contribute: causal masking handles q-side
        # padding; for kv-side padding use an additive -inf via a huge
        # negative key? Simplest correct: mask by zero-value + min-score:
        # set padded K rows to produce -inf scores by making them equal to
        # a large negative multiple of q... safer: fall back to masking via
        # explicit score mask is not in-kernel; instead pad K with -1e4 *
        # unit vectors is fragile -> use oracle path for ragged T.
        if not causal:
            from repro.core.lse_softmax import streaming_attention_ref
            return streaming_attention_ref(q, k, v, causal=False, scale=scale)
    out = flash_attention_kernel(
        q_p, k_p, v_p, causal=causal, scale=scale, bq=bq, bk=bk,
        interpret=(mode == 'interpret'))
    return out[:, :S, :].reshape(B, H, S, d)


# ---------------------------------------------------------------------------
# Fused GroupNorm + swish
# ---------------------------------------------------------------------------

def fused_gn_swish(x: jax.Array, scale: jax.Array, bias: jax.Array, *,
                   groups: int = 32, mode: str | None = None) -> jax.Array:
    mode = mode or _mode()
    C = x.shape[-1]
    g = min(groups, C)
    while C % g:
        g -= 1
    if mode == 'xla':
        return _ref.gn_swish_ref(x, scale, bias, groups=g)
    return fused_gn_swish_kernel(x, scale, bias, groups=g,
                                 interpret=(mode == 'interpret'))
