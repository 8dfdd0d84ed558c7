"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp
oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# ---------------------------------------------------------------------------
# W8A8 matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('M,K,N', [
    (8, 64, 32), (64, 200, 96), (128, 128, 128), (1, 300, 7),
    (257, 129, 65), (16, 1024, 256),
])
def test_w8a8_matches_oracle(M, K, N):
    x = _arr((M, K))
    w = _arr((K, N))
    out_i = ops.w8a8_matmul(x, w, mode='interpret')
    out_x = ops.w8a8_matmul(x, w, mode='xla')
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_x),
                               rtol=0, atol=0)  # bit-identical int path


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_w8a8_close_to_fp(dtype):
    x = _arr((32, 256), dtype)
    w = _arr((256, 64), dtype)
    out = ops.w8a8_matmul(x, w, mode='interpret')
    exact = x.astype(jnp.float32) @ w.astype(jnp.float32)
    rel = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
    assert rel < 0.03, rel     # 8-bit error budget (paper Table I regime)


def test_w8a8_batched_leading_dims():
    x = _arr((2, 3, 96))
    w = _arr((96, 48))
    out = ops.w8a8_matmul(x, w, mode='interpret')
    assert out.shape == (2, 3, 48)


# ---------------------------------------------------------------------------
# Flash attention (streaming LSE softmax)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('S,T,d,causal', [
    (128, 128, 64, False), (128, 128, 64, True),
    (256, 256, 32, True), (128, 384, 64, False),
    (100, 128, 64, True),       # ragged q
])
def test_flash_attention_vs_ref(S, T, d, causal):
    B, H = 2, 3
    q = _arr((B, H, S, d))
    k = _arr((B, H, T, d))
    v = _arr((B, H, T, d))
    if causal and S != T:
        k, v = k[:, :, :S], v[:, :, :S]
        T = S
    out = ops.flash_attention(q, k, v, causal=causal, mode='interpret')
    exp = ref.attention_ref(q.reshape(B * H, S, d), k.reshape(B * H, T, d),
                            v.reshape(B * H, T, d), causal=causal)
    np.testing.assert_allclose(np.asarray(out).reshape(B * H, S, d),
                               np.asarray(exp), atol=2e-5)


@pytest.mark.parametrize('B,H,S,d', [
    (1, 2, 128, 85), (2, 2, 256, 85), (2, 1, 256, 170), (1, 4, 128, 170),
    (1, 2, 256, 256), (3, 1, 128, 256),
])
def test_flash_attention_bf16_operands(B, H, S, d):
    """The UNet's call: q pre-scaled in f32 then q/k/v rounded to bf16,
    ``scale=1``, at the cells' head dims (85, 170, 256), 2-4 batch*heads.
    The oracle runs f32 arithmetic on the same rounded operands, so the
    scores agree to f32 rounding; what differs is ``p = exp(s - m)``
    entering PV as bf16.  Rounding each p_j by a relative 2^-9 moves
    sum_j p_j v_j / l by at most 2^-9 * sum_j p_j |v_j| / l <= 2^-9 *
    max|v| (l sums the unrounded f32 p), hence the tolerance, with 1e-5
    for f32 summation order."""
    q = (_arr((B, H, S, d)) * d ** -0.5).astype(jnp.bfloat16)
    k = _arr((B, H, S, d)).astype(jnp.bfloat16)
    v = _arr((B, H, S, d)).astype(jnp.bfloat16)
    out = ops.flash_attention(q, k, v, scale=1.0, mode='interpret')
    assert out.dtype == jnp.float32
    exp = ref.attention_ref(
        *(x.reshape(B * H, S, d).astype(jnp.float32) for x in (q, k, v)),
        scale=1.0)
    vmax = float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    np.testing.assert_allclose(np.asarray(out).reshape(B * H, S, d),
                               np.asarray(exp), rtol=0,
                               atol=2.0 ** -9 * vmax + 1e-5)


def test_flash_equals_streaming_ref():
    """Kernel == the executable rendering of paper Eq. 4 streaming."""
    from repro.core.lse_softmax import streaming_attention_ref
    q = _arr((2, 2, 128, 32))
    k = _arr((2, 2, 256, 32))
    v = _arr((2, 2, 256, 32))
    a = ops.flash_attention(q, k, v, mode='interpret')
    b = streaming_attention_ref(q, k, v, block=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ---------------------------------------------------------------------------
# Fused GroupNorm + swish
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('N,H,W,C,g', [
    (2, 8, 8, 64, 8), (1, 16, 16, 32, 32), (3, 4, 4, 96, 6),
])
def test_fused_gn_swish(N, H, W, C, g):
    x = _arr((N, H, W, C))
    sc = _arr((C,))
    bi = _arr((C,))
    out = ops.fused_gn_swish(x, sc, bi, groups=g, mode='interpret')
    exp = ref.gn_swish_ref(x, sc, bi, groups=g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)


def test_fused_gn_swish_matches_layer_composition():
    from repro.models import layers as L
    x = _arr((2, 8, 8, 32))
    p = L.init_groupnorm(32)
    fused = ops.fused_gn_swish(x, p['scale'], p['bias'], groups=8,
                               mode='interpret')
    composed = L.swish(L.groupnorm(p, x, groups=8))
    np.testing.assert_allclose(np.asarray(fused), np.asarray(composed),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# analog-noise injection (the engine's w8a8+noise policy)
# ---------------------------------------------------------------------------

@pytest.mark.quant
def test_noisy_w8a8_deterministic_under_key():
    """noisy_w8a8_matmul is a pure function of its key: the same key
    reproduces the same analog draw (the serving engine relies on this
    for reproducible w8a8+noise requests), different keys differ, and
    the whole thing compiles (trace-time crosstalk constant)."""
    from repro.core.photonic.noise import NoiseModel, noisy_w8a8_matmul
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 8, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    k1, k2 = jax.random.PRNGKey(7), jax.random.PRNGKey(8)
    a = noisy_w8a8_matmul(k1, x, w)
    b = noisy_w8a8_matmul(k1, x, w)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = noisy_w8a8_matmul(k2, x, w)
    assert float(jnp.max(jnp.abs(a - c))) > 0.0
    # jit-compiled call agrees with the eager one
    j = jax.jit(lambda k, xx, ww: noisy_w8a8_matmul(k, xx, ww))(k1, x, w)
    np.testing.assert_allclose(np.asarray(j), np.asarray(a), atol=1e-5)


@pytest.mark.quant
def test_noisy_w8a8_collapses_to_plain_w8a8_at_zero_noise():
    """With all noise sigmas ~0 and crosstalk off, the noisy matmul is
    the plain W8A8 matmul."""
    from repro.core.photonic.noise import NoiseModel, noisy_w8a8_matmul
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    quiet = NoiseModel(sigma_w_lsb=0.0, sigma_x_lsb=0.0, sigma_pd_lsb=0.0,
                       crosstalk_db_per_channel=-1000.0)
    y = noisy_w8a8_matmul(jax.random.PRNGKey(0), x, w, model=quiet)
    ref_q = ops.w8a8_matmul(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_q), atol=1e-5)
