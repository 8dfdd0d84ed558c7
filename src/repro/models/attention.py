"""Multi-head attention: GQA (+RoPE / M-RoPE), MLA, KV caches, decode.

Paper hooks:
  * C2 — softmax always goes through the LSE decomposition
    (``repro.core.lse_softmax`` semantics; the Pallas flash kernel on TPU,
    grouped-einsum + ``lse_softmax`` under XLA).
  * C3 — scale folding: 1/sqrt(d_k) is folded into the query projection
    output (free); the (Q W_K^T) X^T reordering is available for
    cross-attention via ``repro.core.attention_decomp``.
  * C1 — ``quant=True`` routes projections through the W8A8 path.

Sharding notes: KV heads are logically replicated ``cfg.kv_repeat`` times so
the head axis shards evenly over the tensor axis (DESIGN.md §4); the grouped
einsum keeps K/V un-repeated per group, so no HBM duplication of the cache
beyond the sharding replicas.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.lse_softmax import lse_softmax
from repro.models import layers as L

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def _rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x (B, S, H, hd), pos (B, S) -> rotated x (half-split convention)."""
    hd = x.shape[-1]
    freqs = _rope_freqs(hd, theta)                       # (hd/2,)
    ang = pos[..., None].astype(jnp.float32) * freqs     # (B, S, hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def mrope(x: jax.Array, pos3: jax.Array, theta: float,
          sections: Tuple[int, ...]) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): pos3 (B, S, 3) are (t, h, w) position ids;
    frequency channels are partitioned into ``sections`` (sum = hd/2), each
    section rotated by its own position stream.  For pure text all three
    streams are equal and M-RoPE == RoPE."""
    hd = x.shape[-1]
    freqs = _rope_freqs(hd, theta)                       # (hd/2,)
    # build per-channel position: (B, S, hd/2)
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.array(sections), total_repeat_length=hd // 2)
    pos_c = jnp.take_along_axis(
        pos3.astype(jnp.float32),
        jnp.broadcast_to(sec_id, pos3.shape[:2] + (hd // 2,)),
        axis=-1)                                          # (B, S, hd/2)
    ang = pos_c * freqs
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def apply_rope(cfg: ArchConfig, x: jax.Array, pos: jax.Array) -> jax.Array:
    if cfg.rope == 'none':
        return x
    if cfg.rope == 'mrope':
        if pos.ndim == 2:  # text-only: broadcast to 3 streams
            pos = jnp.broadcast_to(pos[..., None], pos.shape + (3,))
        return mrope(x, pos, cfg.rope_theta, cfg.mrope_sections)
    return rope(x, pos, cfg.rope_theta)


# ---------------------------------------------------------------------------
# Grouped-query attention core (no KV-head materialization)
# ---------------------------------------------------------------------------

def gqa_core(q: jax.Array, k: jax.Array, v: jax.Array, *,
             causal: bool, q_offset: jax.Array | int = 0,
             kv_len: Optional[jax.Array] = None,
             scale: float | None = None) -> jax.Array:
    """q (B, S, H, hd), k/v (B, T, Hkv, hd) with H = G*rep, Hkv = G.
    Grouped einsum: K/V are never repeated in memory.
    ``kv_len``: number of valid cache rows (decode); ``q_offset``: absolute
    position of q row 0 (causal masking against the cache)."""
    B, S, H, hd = q.shape
    T, G = k.shape[1], k.shape[2]
    rep = H // G
    if scale is None:
        scale = hd ** -0.5
    qg = q.reshape(B, S, G, rep, hd).astype(jnp.float32) * scale
    s = jnp.einsum('bsgrd,btgd->bgrst', qg, k.astype(jnp.float32))
    t_pos = jnp.arange(T)
    mask = jnp.ones((S, T), bool)
    if causal:
        q_pos = jnp.arange(S) + q_offset
        mask = mask & (t_pos[None, :] <= q_pos[:, None])
    if kv_len is not None:
        mask = mask & (t_pos[None, :] < kv_len)
    s = jnp.where(mask, s, NEG_INF)
    p = lse_softmax(s, axis=-1)                           # paper Eq. 4
    out = jnp.einsum('bgrst,btgd->bsgrd', p, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def flash_core(q, k, v, *, causal):
    """TPU Pallas path (inference / prefill).  Repeats KV heads (cheap vs
    the S*T score matrix) and calls the flash kernel."""
    from repro.kernels import ops as kops
    B, S, H, hd = q.shape
    G = k.shape[2]
    kr = jnp.repeat(k, H // G, axis=2)
    vr = jnp.repeat(v, H // G, axis=2)
    out = kops.flash_attention(
        q.transpose(0, 2, 1, 3), kr.transpose(0, 2, 1, 3),
        vr.transpose(0, 2, 1, 3), causal=causal)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.hd
    H, Hkv = cfg.n_heads, cfg.n_kv_heads * cfg.kv_repeat
    ks = jax.random.split(key, 4)
    return {
        'wq': L.init_linear(ks[0], d, H * hd, bias=cfg.attn_bias),
        'wk': L.init_linear(ks[1], d, cfg.n_kv_heads * hd, bias=cfg.attn_bias),
        'wv': L.init_linear(ks[2], d, cfg.n_kv_heads * hd, bias=cfg.attn_bias),
        'wo': L.init_linear(ks[3], H * hd, d, bias=cfg.attn_bias),
    }


def _project_kv(p, cfg: ArchConfig, x_kv: jax.Array, pos: Optional[jax.Array]
                ) -> Tuple[jax.Array, jax.Array]:
    from repro.distributed.sharding import shard_hint
    B, T, _ = x_kv.shape
    hd = cfg.hd
    k = L.linear(p['wk'], x_kv).reshape(B, T, cfg.n_kv_heads, hd)
    v = L.linear(p['wv'], x_kv).reshape(B, T, cfg.n_kv_heads, hd)
    if pos is not None:
        k = apply_rope(cfg, k, pos)
    if cfg.kv_repeat > 1:  # logical replication for even TP sharding
        k = jnp.repeat(k, cfg.kv_repeat, axis=2)
        v = jnp.repeat(v, cfg.kv_repeat, axis=2)
    tp = 'model' if cfg.model_axis_tp else None
    k = shard_hint(k, 'dp', None, tp, None)
    v = shard_hint(v, 'dp', None, tp, None)
    return k, v


def attention(p: Dict[str, Any], cfg: ArchConfig, x: jax.Array, *,
              pos: Optional[jax.Array] = None,
              memory: Optional[jax.Array] = None,
              cache: Optional[Dict[str, jax.Array]] = None,
              cache_pos: Optional[jax.Array] = None,
              causal: bool = True,
              impl: str = 'xla',
              quant: bool = False) -> Tuple[jax.Array, Optional[Dict]]:
    """One attention layer.

    modes:
      * train / no-cache forward:       cache=None
      * prefill (fills cache):          cache=empty dict of buffers, cache_pos=0
      * decode (1 token, reads cache):  cache=filled, cache_pos=current length
    ``memory`` switches to cross-attention (no cache, not causal).
    Returns (out, new_cache).
    """
    B, S, d = x.shape
    hd, H = cfg.hd, cfg.n_heads
    from repro.distributed.sharding import shard_hint
    if pos is None:
        pos = jnp.arange(S)[None, :] + (0 if cache_pos is None else cache_pos)
        pos = jnp.broadcast_to(pos, (B, S))
    tp = 'model' if cfg.model_axis_tp else None
    x = shard_hint(x, 'dp', None, None)
    q = L.linear(p['wq'], x, quant=quant).reshape(B, S, H, hd)
    q = shard_hint(q, 'dp', None, tp, None)
    q = apply_rope(cfg, q, pos)

    if memory is not None:                       # cross-attention
        k, v = _project_kv(p, cfg, memory, None)
        out = gqa_core(q, k, v, causal=False)
        new_cache = cache
    elif cache is None:                          # plain causal self-attn
        k, v = _project_kv(p, cfg, x, pos)
        if impl == 'pallas':
            out = flash_core(q, k, v, causal=causal)
        else:
            out = gqa_core(q, k, v, causal=causal)
        new_cache = None
    else:                                        # prefill or decode
        k, v = _project_kv(p, cfg, x, pos)
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache['k'], k.astype(cache['k'].dtype), cache_pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache['v'], v.astype(cache['v'].dtype), cache_pos, axis=1)
        new_cache = {'k': ck, 'v': cv}
        kv_len = cache_pos + S
        out = gqa_core(q, ck, cv, causal=True, q_offset=cache_pos,
                       kv_len=kv_len)
    from repro.distributed.sharding import shard_hint as _sh
    out = _sh(out, 'dp', None, 'model' if cfg.model_axis_tp else None, None)
    y = L.linear(p['wo'], out.reshape(B, S, H * hd), quant=quant)
    y = _sh(y, 'dp', None, None)
    return y, new_cache


def init_attention_cache(cfg: ArchConfig, batch: int, max_len: int,
                         dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    Hkv = cfg.n_kv_heads * cfg.kv_repeat
    shape = (batch, max_len, Hkv, cfg.hd)
    return {'k': jnp.zeros(shape, dtype), 'v': jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): compressed KV cache
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ArchConfig) -> Dict[str, Any]:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        'wq': L.init_linear(ks[0], d, H * qk_dim, bias=False),
        'w_dkv': L.init_linear(ks[1], d, m.kv_lora_rank, bias=False),
        'w_kpe': L.init_linear(ks[2], d, m.qk_rope_head_dim, bias=False),
        'w_uk': L.init_linear(ks[3], m.kv_lora_rank,
                              H * m.qk_nope_head_dim, bias=False),
        'w_uv': L.init_linear(ks[4], m.kv_lora_rank,
                              H * m.v_head_dim, bias=False),
        'wo': L.init_linear(ks[5], H * m.v_head_dim, d, bias=False),
        'kv_norm': L.init_rmsnorm(m.kv_lora_rank),
    }


def mla_attention(p, cfg: ArchConfig, x: jax.Array, *,
                  pos: Optional[jax.Array] = None,
                  cache: Optional[Dict[str, jax.Array]] = None,
                  cache_pos: Optional[jax.Array] = None,
                  quant: bool = False,
                  impl: str = 'xla') -> Tuple[jax.Array, Optional[Dict]]:
    """MLA with compressed-KV cache.  Prefill/train uses the naive
    (decompress) path; decode uses the *absorbed* path (q projected into the
    latent space — the MLA analogue of paper Eq. 6 reordering), so the cache
    holds only (c_kv, k_pe)."""
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.n_heads
    nope, rpe, vd, rank = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                           m.v_head_dim, m.kv_lora_rank)
    if pos is None:
        pos = jnp.arange(S)[None, :] + (0 if cache_pos is None else cache_pos)
        pos = jnp.broadcast_to(pos, (B, S))
    from repro.distributed.sharding import shard_hint
    tp = 'model' if cfg.model_axis_tp else None
    x = shard_hint(x, 'dp', None, None)
    q = L.linear(p['wq'], x, quant=quant).reshape(B, S, H, nope + rpe)
    q = shard_hint(q, 'dp', None, tp, None)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = rope(q_pe, pos, cfg.rope_theta)
    c_kv = L.rmsnorm(p['kv_norm'], L.linear(p['w_dkv'], x, quant=quant))
    k_pe = rope(L.linear(p['w_kpe'], x, quant=quant)[:, :, None, :],
                pos, cfg.rope_theta)[:, :, 0, :]          # (B, S, rpe)
    scale = (nope + rpe) ** -0.5

    decode = cache is not None and cache_pos is not None
    if decode:
        cc = jax.lax.dynamic_update_slice_in_dim(
            cache['c_kv'], c_kv.astype(cache['c_kv'].dtype), cache_pos, 1)
        cp = jax.lax.dynamic_update_slice_in_dim(
            cache['k_pe'], k_pe.astype(cache['k_pe'].dtype), cache_pos, 1)
        new_cache = {'c_kv': cc, 'k_pe': cp}
        T = cc.shape[1]
        kv_len = cache_pos + S
        # absorbed path: q_nope' = q_nope @ W_uk^T  -> latent space
        from repro.core.quantization import QTensor as _QT
        _raw = lambda w: (w.dequantize(jnp.float32)
                          if isinstance(w, _QT) else w)
        w_uk = _raw(p['w_uk']['w']).reshape(rank, H, nope)
        q_lat = jnp.einsum('bshn,rhn->bshr', q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))      # (B,S,H,rank)
        s = (jnp.einsum('bshr,btr->bhst', q_lat,
                        cc.astype(jnp.float32)) +
             jnp.einsum('bshp,btp->bhst', q_pe.astype(jnp.float32),
                        cp.astype(jnp.float32))) * scale
        t_pos = jnp.arange(T)
        q_pos = jnp.arange(S) + cache_pos
        mask = (t_pos[None, :] <= q_pos[:, None]) & (t_pos[None, :] < kv_len)
        s = jnp.where(mask, s, NEG_INF)
        pr = lse_softmax(s, axis=-1)
        o_lat = jnp.einsum('bhst,btr->bshr', pr, cc.astype(jnp.float32))
        w_uv = _raw(p['w_uv']['w']).reshape(rank, H, vd)
        out = jnp.einsum('bshr,rhv->bshv', o_lat, w_uv.astype(jnp.float32))
    else:
        new_cache = None
        k_nope = L.linear(p['w_uk'], c_kv).reshape(B, S, H, nope)
        vv = L.linear(p['w_uv'], c_kv).reshape(B, S, H, vd)
        s = (jnp.einsum('bshn,bthn->bhst', q_nope.astype(jnp.float32),
                        k_nope.astype(jnp.float32)) +
             jnp.einsum('bshp,btp->bhst', q_pe.astype(jnp.float32),
                        k_pe.astype(jnp.float32))) * scale
        t_pos = jnp.arange(S)
        mask = t_pos[None, :] <= t_pos[:, None]
        s = jnp.where(mask, s, NEG_INF)
        pr = lse_softmax(s, axis=-1)
        out = jnp.einsum('bhst,bthv->bshv', pr, vv.astype(jnp.float32))
    out = shard_hint(out.astype(x.dtype), 'dp', None, tp, None)
    y = L.linear(p['wo'], out.reshape(B, S, H * vd), quant=quant)
    return shard_hint(y, 'dp', None, None), new_cache


def init_mla_cache(cfg: ArchConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    m = cfg.mla
    return {'c_kv': jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            'k_pe': jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype)}
