"""Pallas TPU kernel: flash attention with the paper's streaming LSE softmax.

DiffLight (C2) digitizes attention scores as they stream out of the MR banks
and *concurrently* tracks gamma_max with a comparator, accumulating
ln-sum-exp via LUTs (Eq. 4).  Blockwise in VMEM, that pipeline is exactly the
online-softmax recurrence:

    m'   = max(m, max_j s_j)            # comparator
    l'   = l * e^(m-m') + sum_j e^(s_j - m')   # LUT exp + accumulate
    acc' = acc * e^(m-m') + P V_blk     # MR bank no.7 of the attention head
    out  = acc / l                      # ops 2+3 of Eq. 4 (ln + subtract)

Layout: q (B, S, H*d), k/v (B, T, H*d), the heads side by side on the lanes
as a projection writes them, so no transpose or padding of the head dim
runs outside the kernel; a block holds a group of hg heads whose lanes fill
whole 128-lane tiles (all H where no smaller group does) and the kernel
slices them.  Grid: (B / bb, H / hg, nq, nk) with the KV loop innermost;
(m, l, acc) live in VMEM scratch across KV steps.  Where the whole key
sequence fits one block (nk == 1) the recurrence is a single step and needs
no scratch.
Causal blocks beyond the diagonal are skipped (grid-level work elision — the
photonic analogue is not lighting idle banks).

The MXU operand dtype is the inputs' own: bfloat16 q/k/v (and ``p``, cast
before PV) multiply as bfloat16, float32 inputs as float32.  Both dots
accumulate in float32; ``m``, ``l``, the accumulator, the final division
and the output are float32 whatever the inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: VMEM bytes one grid step may fill (blocks, scores, scratch), below the
#: 16 MiB Mosaic scopes by default on a v5e
VMEM_BUDGET = 12 << 20

#: longest key sequence taken as one block (one pass, no rescaling)
WHOLE_T_MAX = 1024


def vmem_bytes(bb: int, bq: int, bk: int, heads: int, width: int,
               itemsize: int, nk: int) -> int:
    """VMEM of one grid step over ``heads`` heads, rows of ``width`` lanes
    rounded up to 128:
    double-buffered q/k/v blocks in the input dtype and the f32 output
    block, one head's f32 score tile three times over (s, p and p's MXU
    copy), and the (m, l, acc) scratch when nk > 1 (m and l padded to 128
    lanes)."""
    w = -(-width // 128) * 128
    io = 2 * bb * ((bq + 2 * bk) * w * itemsize + bq * w * 4)
    scores = 3 * bb * bq * bk * 4
    scratch = bb * bq * (2 * heads * 128 + w) * 4 if nk > 1 else 0
    return io + scores + scratch


def blocks(B: int, S: int, T: int, heads: int, d: int, itemsize: int,
           causal: bool):
    """(bb, hg, bq, bk) for q (B, S, heads*d), k/v (B, T, heads*d): hg, the
    fewest heads whose lanes fill whole 128-lane tiles (else all).  Causal:
    one row of 128x128 blocks, so the diagonal skip elides half the work.
    Otherwise the whole of T when it is at most ``WHOLE_T_MAX`` (else the
    largest of 512/256 dividing it, else 128), the largest q block of
    512/256 dividing S that fits ``VMEM_BUDGET`` (else 128; all of S below
    128), then as many batch rows per block as the budget allows."""
    hg = next((g for g in range(1, heads + 1)
               if heads % g == 0 and g * d % 128 == 0), heads)
    if causal:
        return 1, hg, min(128, S), min(128, T)
    bk = T if T <= WHOLE_T_MAX else next(
        (b for b in (512, 256) if T % b == 0), 128)
    nk = -(-T // bk)

    def fits(bb, bq):
        return vmem_bytes(bb, bq, bk, hg, hg * d, itemsize,
                          nk) <= VMEM_BUDGET

    bq = S if S < 128 else next(
        (b for b in (512, 256) if S % b == 0 and fits(1, b)), 128)
    bb = 1
    while B % (2 * bb) == 0 and fits(2 * bb, bq):
        bb *= 2
    return bb, hg, bq, bk


def _scores(q_ref, k_ref, sl, qi, ki, *, scale, causal):
    """(bb, bq, bk) f32 scores of one head (lanes ``sl``) of the q and k
    blocks, multiplied in their dtype."""
    q = q_ref[:, :, sl]
    if scale != 1.0:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    s = jnp.einsum('bqd,bkd->bqk', q, k_ref[:, :, sl],
                   preferred_element_type=jnp.float32)
    if causal:
        _, bq, bk = s.shape
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)
    return s


def _pv(p, v_ref, sl):
    return jnp.einsum('bqk,bkd->bqd', p.astype(v_ref.dtype),
                      v_ref[:, :, sl], preferred_element_type=jnp.float32)


def _finish(o_ref, sl, acc, l):
    o_ref[:, :, sl] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _one_pass_kernel(q_ref, k_ref, v_ref, o_ref, *, heads, scale, causal):
    d = q_ref.shape[-1] // heads
    for h in range(heads):
        sl = slice(h * d, (h + 1) * d)
        s = _scores(q_ref, k_ref, sl, pl.program_id(2), 0, scale=scale,
                    causal=causal)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        _finish(o_ref, sl, _pv(p, v_ref, sl),
                jnp.sum(p, axis=-1, keepdims=True))


def _streaming_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                      heads, scale, causal, nk, bq, bk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    d = q_ref.shape[-1] // heads

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step():
        for h in range(heads):
            sl = slice(h * d, (h + 1) * d)
            s = _scores(q_ref, k_ref, sl, qi, ki, scale=scale, causal=causal)
            m_prev = m_ref[h]                             # (bb, bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                        # (bb, bq, bk)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, -1, keepdims=True)
            acc_ref[:, :, sl] = acc_ref[:, :, sl] * corr + _pv(p, v_ref, sl)
            m_ref[h] = m_new

    if causal:
        # skip fully-masked blocks (k block strictly after q block)
        pl.when(ki * bk <= qi * bq + bq - 1)(_step)
    else:
        _step()

    @pl.when(ki == nk - 1)
    def _finalize():
        for h in range(heads):
            sl = slice(h * d, (h + 1) * d)
            _finish(o_ref, sl, acc_ref[:, :, sl], l_ref[h])


@functools.partial(jax.jit,
                   static_argnames=('heads', 'causal', 'scale', 'bb', 'hg',
                                    'bq', 'bk', 'interpret'))
def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           bb: int, hg: int, bq: int, bk: int,
                           heads: int = 1, causal: bool = False,
                           scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """q (B, S, heads*d), k/v (B, T, heads*d) -> (B, S, heads*d) float32,
    in (bb, bq|bk, hg*d) blocks (``blocks`` chooses them); B % bb == 0,
    heads % hg == 0, S % bq == 0, T % bk == 0 (ops.py pads)."""
    B, S, W = q.shape
    T = k.shape[1]
    assert (B % bb == 0 and heads % hg == 0 and S % bq == 0 and T % bk == 0
            and W % heads == 0), (B, S, T, W, heads, bb, hg, bq, bk)
    w = W // heads * hg
    if scale is None:
        scale = (W // heads) ** -0.5
    nq, nk = S // bq, T // bk
    if nk == 1:
        kern = functools.partial(_one_pass_kernel, heads=hg, scale=scale,
                                 causal=causal)
        scratch = []
    else:
        kern = functools.partial(_streaming_kernel, heads=hg, scale=scale,
                                 causal=causal, nk=nk, bq=bq, bk=bk)
        scratch = [pltpu.VMEM((hg, bb, bq, 1), jnp.float32),   # m
                   pltpu.VMEM((hg, bb, bq, 1), jnp.float32),   # l
                   pltpu.VMEM((bb, bq, w), jnp.float32)]       # acc
    return pl.pallas_call(
        kern,
        grid=(B // bb, heads // hg, nq, nk),
        in_specs=[
            pl.BlockSpec((bb, bq, w), lambda b, g, i, j: (b, i, g)),
            pl.BlockSpec((bb, bk, w), lambda b, g, i, j: (b, j, g)),
            pl.BlockSpec((bb, bk, w), lambda b, g, i, j: (b, j, g)),
        ],
        out_specs=pl.BlockSpec((bb, bq, w), lambda b, g, i, j: (b, i, g)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
