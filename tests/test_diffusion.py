"""Diffusion substrate tests: schedules, samplers, UNet, pipeline, Table-I
parameter counts and W8A8 quality proxy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.diffusion import PAPER_MODELS, PAPER_PARAM_COUNTS
from repro.diffusion.samplers import ddim_sample, ddpm_sample, ddpm_step
from repro.diffusion.schedule import (cosine_schedule, ddpm_loss,
                                      linear_schedule, q_sample)
from repro.models.unet import UNetConfig, init_unet, unet_apply

TINY = UNetConfig('tiny', img_size=16, in_ch=3, base_ch=32, ch_mults=(1, 2),
                  n_res_blocks=1, attn_resolutions=(8,), n_heads=4,
                  timesteps=16)


@pytest.mark.smoke
def test_schedule_monotone():
    s = linear_schedule(100)
    ab = np.asarray(s.alpha_bars)
    assert np.all(np.diff(ab) < 0) and ab[0] < 1.0 and ab[-1] > 0.0
    c = cosine_schedule(100)
    assert np.all(np.asarray(c.betas) >= 0)


def test_forward_process_snr():
    """Eq. 1: signal-to-noise decays to ~0 at t=T-1."""
    s = linear_schedule(1000)
    x0 = jnp.ones((2, 4, 4, 1))
    noise = jax.random.normal(jax.random.PRNGKey(0), x0.shape)
    x_late = q_sample(s, x0, jnp.array([999, 999]), noise)
    # at t=T the sample is essentially pure noise
    corr = np.corrcoef(np.asarray(x_late).ravel(),
                       np.asarray(noise).ravel())[0, 1]
    assert corr > 0.98


@pytest.mark.parametrize('cfgname', list(PAPER_MODELS))
def test_table1_param_counts(cfgname):
    """UNet hyper-params reproduce Table I parameter counts to <0.5%."""
    cfg = PAPER_MODELS[cfgname]
    shapes = jax.eval_shape(lambda k: init_unet(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(s.shape)) for s in
            jax.tree_util.tree_leaves(shapes))
    target = PAPER_PARAM_COUNTS[cfgname] * 1e6
    assert abs(n - target) / target < 0.005, (cfgname, n / 1e6)


def test_unet_shapes_and_finiteness():
    p = init_unet(jax.random.PRNGKey(0), TINY)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    eps = unet_apply(p, TINY, x, jnp.array([3, 9]))
    assert eps.shape == x.shape
    assert np.all(np.isfinite(np.asarray(eps)))


def test_unet_sparse_dataflow_equivalence():
    """C4 toggle changes the dataflow, not the function."""
    import dataclasses
    p = init_unet(jax.random.PRNGKey(0), TINY)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 3))
    t = jnp.array([5])
    a = unet_apply(p, TINY, x, t)
    b = unet_apply(p, dataclasses.replace(TINY, sparse_dataflow=False),
                   x, t)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize('base_ch,n_heads,context_dim,calls', [
    (32, 2, 24, 3),       # 256 tokens, heads 16 wide: the kernel
    (256, 1, None, 0),    # 256 tokens, one head 256 wide: the einsum
])
def test_unet_self_attention_dispatches_to_flash_kernel(
        monkeypatch, base_ch, n_heads, context_dim, calls):
    """Off ``xla`` mode, exactly the self-attention calls of 256 tokens
    (16x16: one down block, two up blocks) whose heads are narrower than
    that reach the flash kernel; the 8x8 mid-block's 64 tokens, every
    7-token cross-attention, and heads as wide as the sequence (the DDPM
    shape) stay on the einsum.  The output matches the ``xla``-mode UNet
    to within 2^-8 relative L2, one bfloat16 rounding of the attention
    operands (the CPU einsum multiplies in float32)."""
    import re
    cfg = UNetConfig('tiny_flash', img_size=16, in_ch=4, base_ch=base_ch,
                     ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(16,),
                     n_heads=n_heads, context_dim=context_dim, groups=8,
                     timesteps=16)
    p = init_unet(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 4))
    t = jnp.array([3, 9])
    ctx = None if context_dim is None else jax.random.normal(
        jax.random.PRNGKey(2), (2, 7, context_dim))
    out = {}
    for mode in ('interpret', 'xla'):
        monkeypatch.setenv('REPRO_KERNELS', mode)
        step = jax.jit(lambda p, x, t, c: unet_apply(p, cfg, x, t, c))
        text = step.lower(p, x, t, ctx).as_text()
        found = len(re.findall(r'call @flash_attention_kernel', text))
        assert found == (calls if mode == 'interpret' else 0), (mode, found)
        out[mode] = step(p, x, t, ctx)
    rel = float(jnp.linalg.norm(out['interpret'] - out['xla'])
                / jnp.linalg.norm(out['xla']))
    assert rel < 2.0 ** -8, rel


def test_ddpm_training_reduces_loss():
    sched = linear_schedule(TINY.timesteps)
    p = init_unet(jax.random.PRNGKey(0), TINY)
    x0 = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16, 3)) * 0.5

    def apply_fn(params, x, t, ctx):
        return unet_apply(params, TINY, x, t, ctx)

    # the per-step loss is noisy (random t, random noise) — evaluate with a
    # FIXED key before/after training so the comparison is deterministic
    eval_key = jax.random.PRNGKey(123)

    @jax.jit
    def evaluate(params):
        return ddpm_loss(apply_fn, sched, params, x0, eval_key)

    @jax.jit
    def step(params, key):
        loss, g = jax.value_and_grad(
            lambda q: ddpm_loss(apply_fn, sched, q, x0, key))(params)
        params = jax.tree_util.tree_map(lambda a, b: a - 3e-3 * b,
                                        params, g)
        return params, loss
    before = float(evaluate(p))
    key = jax.random.PRNGKey(2)
    for i in range(25):
        key, k = jax.random.split(key)
        p, _ = step(p, k)
    after = float(evaluate(p))
    assert after < before, (before, after)


def test_samplers_produce_finite_images():
    sched = linear_schedule(TINY.timesteps)
    p = init_unet(jax.random.PRNGKey(0), TINY)

    def eps_fn(x, t):
        return unet_apply(p, TINY, x, t)
    img = jax.jit(lambda k: ddim_sample(sched, eps_fn, (2, 16, 16, 3), k,
                                        steps=4))(jax.random.PRNGKey(3))
    assert img.shape == (2, 16, 16, 3)
    assert np.all(np.isfinite(np.asarray(img)))


def test_ddpm_step_variance():
    """Eq. 2: at t=0 no noise is re-added (deterministic final step)."""
    sched = linear_schedule(16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 1))
    eps_fn = lambda xx, tt: jnp.zeros_like(xx)
    a = ddpm_step(sched, eps_fn, x, 0, jax.random.PRNGKey(1))
    b = ddpm_step(sched, eps_fn, x, 0, jax.random.PRNGKey(2))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_w8a8_unet_quality_proxy():
    """Table-I proxy: W8A8 UNet output stays close to fp32 (relative L2 on
    the predicted noise, the quantity that drives IS changes)."""
    p = init_unet(jax.random.PRNGKey(0), TINY)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    t = jnp.array([5, 11])
    a = unet_apply(p, TINY, x, t, quant=False)
    b = unet_apply(p, TINY, x, t, quant=True)
    rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
    assert rel < 0.10, rel


def test_deepcache_baseline():
    """DeepCache (the paper's algorithmic baseline [21]): refresh pass is
    bit-identical to the full UNet; skip steps reuse deep features with
    bounded drift and strictly fewer MACs."""
    import dataclasses
    from repro.diffusion.deepcache import (deepcache_workload_factor,
                                           unet_apply_cached)
    cfg = dataclasses.replace(TINY, ch_mults=(1, 2, 2))
    p = init_unet(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    t = jnp.array([5, 5])
    full = unet_apply(p, cfg, x, t)
    eps_r, cache = unet_apply_cached(p, cfg, x, t, None, refresh=True)
    np.testing.assert_allclose(np.asarray(eps_r), np.asarray(full), atol=0)
    x2 = x + 0.05 * jax.random.normal(jax.random.PRNGKey(2), x.shape)
    full2 = unet_apply(p, cfg, x2, jnp.array([4, 4]))
    eps_s, _ = unet_apply_cached(p, cfg, x2, jnp.array([4, 4]), cache,
                                 refresh=False)
    rel = float(jnp.linalg.norm(eps_s - full2) / jnp.linalg.norm(full2))
    assert rel < 0.2, rel
    f = deepcache_workload_factor(cfg, interval=5)
    assert 0.1 < f < 0.9


# ---------------------------------------------------------------------------
# precision-policy API (replaces the bare quant flag)
# ---------------------------------------------------------------------------

@pytest.mark.quant
def test_precision_policy_equals_deprecated_quant_flag():
    """policy=PrecisionPolicy.w8a8() and the deprecated quant=True build
    the SAME graph — bit-identical outputs — and the boolean spelling
    warns."""
    from repro.core.precision import PrecisionPolicy, resolve
    p = init_unet(jax.random.PRNGKey(0), TINY)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    t = jnp.array([5, 11])
    with pytest.warns(DeprecationWarning):
        old = unet_apply(p, TINY, x, t, quant=True)
    new = unet_apply(p, TINY, x, t, policy=PrecisionPolicy.w8a8())
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))
    with pytest.warns(DeprecationWarning):
        assert resolve(None, True) == PrecisionPolicy.w8a8()


@pytest.mark.quant
def test_precision_policy_validation_and_names():
    from repro.core.precision import (PRECISION_NAMES, PrecisionPolicy,
                                      resolve)
    assert set(PRECISION_NAMES) == {'fp32', 'w8a8', 'w8a8+noise'}
    for name in PRECISION_NAMES:
        pol = PrecisionPolicy.from_name(name)
        assert pol.name == name
        assert resolve(name) == pol              # str spelling resolves too
    with pytest.raises(ValueError):
        PrecisionPolicy.from_name('int4')
    with pytest.raises(ValueError):
        PrecisionPolicy(backend='fp8')
    with pytest.raises(ValueError):
        # noise model requires the quantized backend
        from repro.core.photonic.noise import NoiseModel
        PrecisionPolicy(backend='fp32', noise=NoiseModel())
    # frozen + hashable: usable as a jit closure / dict key
    assert hash(PrecisionPolicy.w8a8()) == hash(PrecisionPolicy.w8a8())


@pytest.mark.quant
def test_prequantize_calibration_matches_dynamic():
    """Serve-time calibration: prequantized weights agree with the
    dynamic w8a8 path to ~1 LSB (XLA constant-folds the in-graph weight
    quantization differently, flipping round-tie int8 values)."""
    from repro.core.precision import PrecisionPolicy
    from repro.core.quantization import QTensor
    from repro.diffusion.pipeline import DiffusionPipeline
    pipe = DiffusionPipeline.init(jax.random.PRNGKey(0), TINY,
                                  policy=PrecisionPolicy.w8a8())
    pq = pipe.prequantize()
    assert pq.policy.calibration == 'prequant'
    n_q = sum(isinstance(l, QTensor) for l in
              jax.tree_util.tree_leaves(
                  pq.unet_params,
                  is_leaf=lambda l: isinstance(l, QTensor)))
    assert n_q > 0                       # attn projections became QTensors
    a = pipe.generate(jax.random.PRNGKey(3), batch=1, steps=3)
    b = pq.generate(jax.random.PRNGKey(3), batch=1, steps=3)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)


@pytest.mark.quant
def test_noisy_policy_deterministic_in_pipeline():
    """w8a8+noise generation is reproducible under the policy's seed and
    differs across seeds."""
    from repro.core.precision import PrecisionPolicy
    from repro.diffusion.pipeline import DiffusionPipeline
    pipe = DiffusionPipeline.init(jax.random.PRNGKey(0), TINY)
    p0 = PrecisionPolicy.w8a8_noise(noise_seed=0)
    p1 = PrecisionPolicy.w8a8_noise(noise_seed=1)
    a = pipe.generate(jax.random.PRNGKey(2), batch=1, steps=3, policy=p0)
    b = pipe.generate(jax.random.PRNGKey(2), batch=1, steps=3, policy=p0)
    c = pipe.generate(jax.random.PRNGKey(2), batch=1, steps=3, policy=p1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.max(jnp.abs(a - c))) > 0.0
    # and stays within the analog error envelope of the clean w8a8 path
    q = pipe.generate(jax.random.PRNGKey(2), batch=1, steps=3,
                      policy=PrecisionPolicy.w8a8())
    rel = float(jnp.linalg.norm(a - q) / jnp.linalg.norm(q))
    assert rel < 0.05, rel


# ---------------------------------------------------------------------------
# cache-aware scheduling substrate (DeepCache parity, trajectory edges)
# ---------------------------------------------------------------------------

@pytest.mark.sched
def test_generate_deepcache_interval1_matches_generate():
    """With interval=1 every DeepCache step is a refresh, and refresh is
    bit-identical to the full UNet pass — so the whole trajectory must
    reproduce the plain DDIM pipeline."""
    from repro.diffusion.pipeline import DiffusionPipeline
    pipe = DiffusionPipeline.init(jax.random.PRNGKey(0), TINY)
    key = jax.random.PRNGKey(5)
    a = pipe.generate(key, batch=2, steps=4)
    b = pipe.generate_deepcache(key, batch=2, steps=4, interval=1)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                               atol=1e-5, rtol=0)
    # and a caching run with the same seed stays in the same ballpark
    c = pipe.generate_deepcache(key, batch=2, steps=4, interval=2)
    rel = float(jnp.linalg.norm(c - a) / jnp.linalg.norm(a))
    assert rel < 0.5, rel


@pytest.mark.sched
@pytest.mark.quant
def test_unet_apply_cached_under_w8a8_policy():
    """The cached fast path composes with the precision-policy API: a
    w8a8 refresh pass is bit-identical to the w8a8 full pass, and the
    skip pass stays within the quantization drift envelope."""
    import dataclasses
    from repro.core.precision import PrecisionPolicy
    from repro.diffusion.deepcache import unet_apply_cached
    cfg = dataclasses.replace(TINY, ch_mults=(1, 2, 2))
    p = init_unet(jax.random.PRNGKey(0), cfg)
    pol = PrecisionPolicy.w8a8()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    t = jnp.array([5, 5])
    full_q = unet_apply(p, cfg, x, t, policy=pol)
    eps_r, cache = unet_apply_cached(p, cfg, x, t, None, refresh=True,
                                     policy=pol)
    np.testing.assert_allclose(np.asarray(eps_r), np.asarray(full_q),
                               atol=0)
    x2 = x + 0.05 * jax.random.normal(jax.random.PRNGKey(2), x.shape)
    full2 = unet_apply(p, cfg, x2, jnp.array([4, 4]), policy=pol)
    eps_s, _ = unet_apply_cached(p, cfg, x2, jnp.array([4, 4]), cache,
                                 refresh=False, policy=pol)
    assert np.all(np.isfinite(np.asarray(eps_s)))
    rel = float(jnp.linalg.norm(eps_s - full2) / jnp.linalg.norm(full2))
    assert rel < 0.25, rel


@pytest.mark.sched
@pytest.mark.smoke
def test_ddim_timesteps_edges():
    """The single trajectory source every consumer reads: steps=1 jumps
    straight from T-1, steps=T visits every timestep, and interior
    counts are strictly decreasing T-1 ... 0 (no duplicate endpoints)."""
    from repro.diffusion.samplers import ddim_timesteps
    sched = linear_schedule(16)
    one = ddim_timesteps(sched, 1)
    assert one.dtype == np.int32 and one.tolist() == [15]
    full = ddim_timesteps(sched, 16)
    assert full.tolist() == list(range(15, -1, -1))
    for steps in (2, 3, 5, 7, 16):
        ts = ddim_timesteps(sched, steps)
        assert len(ts) == steps
        assert ts[0] == 15 and ts[-1] == 0
        assert np.all(np.diff(ts) < 0), ts
