"""Continuous-batching serving engine tests: numerical equivalence with
sequential per-request sampling, zero recompilation after warmup,
scheduler completeness under staggered arrivals, metric monotonicity,
queue priorities and the slot/bucket policies."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.diffusion.pipeline import DiffusionPipeline
from repro.diffusion.samplers import ddim_sample, ddim_step, ddim_timesteps
from repro.diffusion.schedule import linear_schedule
from repro.models.autoencoder import VAEConfig
from repro.models.unet import UNetConfig
from repro.core.precision import PrecisionPolicy
from repro.serving import (AdmissionQueue, ContinuousBatchingEngine,
                           GenerationRequest, PhotonicAccountant,
                           BucketRouter, bucket_for, choose_slots,
                           group_by_precision)

TINY = UNetConfig('tiny-serve', img_size=16, in_ch=3, base_ch=32,
                  ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(8,),
                  n_heads=4, timesteps=16)


@pytest.fixture(scope='module')
def pipe():
    return DiffusionPipeline.init(jax.random.PRNGKey(0), TINY)


def _drive(engine, submits, max_ticks=200):
    """Logical-clock loop: ``submits`` maps tick index -> requests."""
    results, now = [], 0.0
    for k in range(max_ticks):
        for req in submits.get(k, ()):
            assert engine.submit(req, now=now)
        results.extend(engine.tick(now=now))
        now += 1.0
        if engine.busy:
            continue
        if all(t <= k for t in submits):
            return results
    raise AssertionError('engine did not drain')


# ---------------------------------------------------------------------------
# ddim_step refactor
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_ddim_step_vectorizes_per_sample_timesteps():
    """One mixed-timestep call == per-sample scalar-timestep calls."""
    sched = linear_schedule(32)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (3, 4, 4, 2))
    eps = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    t = jnp.array([30, 17, 2], jnp.int32)
    t_prev = jnp.array([17, 2, -1], jnp.int32)
    mixed = ddim_step(sched, eps, x, t, t_prev)
    for b in range(3):
        one = ddim_step(sched, eps[b:b + 1], x[b:b + 1],
                        int(t[b]), int(t_prev[b]))
        np.testing.assert_allclose(np.asarray(mixed[b]),
                                   np.asarray(one[0]), atol=1e-6)


def test_ddim_sample_unchanged_by_refactor():
    """ddim_sample still denoises pure noise toward the data scale."""
    sched = linear_schedule(32)
    out = ddim_sample(sched, lambda x, t: jnp.zeros_like(x), (2, 4, 4, 1),
                      jax.random.PRNGKey(0), steps=8)
    assert np.all(np.isfinite(np.asarray(out)))


# ---------------------------------------------------------------------------
# engine correctness
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_mixed_timestep_equals_sequential_sampling(pipe):
    """Staggered requests with DIFFERENT step counts, multiplexed through
    shared mixed-timestep steps, must match per-request sequential DDIM
    (DiffusionPipeline.generate, batch=1) at atol 1e-5."""
    engine = ContinuousBatchingEngine(pipe, slots=3)
    reqs = [GenerationRequest(i, seed=100 + i, steps=s)
            for i, s in enumerate([3, 5, 4, 2])]
    # 4 requests into 3 slots, staggered over the first ticks
    results = _drive(engine, {0: reqs[:2], 1: [reqs[2]], 3: [reqs[3]]})
    assert sorted(r.request_id for r in results) == [0, 1, 2, 3]
    for r in results:
        ref = pipe.generate(jax.random.PRNGKey(100 + r.request_id),
                            batch=1, steps=r.steps)
        np.testing.assert_allclose(r.image, np.asarray(ref[0]), atol=1e-5)


def test_engine_guided_slots_match_pipeline_guidance():
    """Per-slot classifier-free guidance: a guided and an unguided
    request sharing ticks each match their sequential counterpart, and
    the guided tick variant compiles exactly once at warmup."""
    cfg = UNetConfig('tiny-sdm', img_size=16, in_ch=3, base_ch=32,
                     ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(8,),
                     n_heads=4, timesteps=16, context_dim=8)
    p = DiffusionPipeline.init(jax.random.PRNGKey(0), cfg)
    ctx1 = jax.random.normal(jax.random.PRNGKey(9), (1, 4, 8))
    ctx = jnp.tile(ctx1, (2, 1, 1))                   # same text, 2 slots
    engine = ContinuousBatchingEngine(p, slots=2, context=ctx)
    engine.warmup()
    warm = engine.compile_stats()
    assert warm.get('step_fp32_guided', 0) == 1
    reqs = [GenerationRequest(0, seed=11, steps=3, guidance=2.5),
            GenerationRequest(1, seed=12, steps=3)]
    results = _drive(engine, {0: reqs})
    assert engine.compile_stats() == warm
    for r in results:
        req = reqs[r.request_id]
        ref = p.generate(jax.random.PRNGKey(req.seed), batch=1,
                         steps=req.steps, context=ctx1,
                         guidance=req.guidance)
        np.testing.assert_allclose(r.image, np.asarray(ref[0]), atol=1e-5)


def test_engine_with_vae_matches_pipeline():
    vae = VAEConfig(img_size=16, in_ch=3, z_ch=4, base_ch=16,
                    ch_mults=(1, 2), groups=8)
    unet = UNetConfig('tiny-ldm', img_size=8, in_ch=4, base_ch=32,
                      ch_mults=(1, 2), n_res_blocks=1,
                      attn_resolutions=(4,), n_heads=4, timesteps=16,
                      latent=True)
    p = DiffusionPipeline.init(jax.random.PRNGKey(0), unet, vae_cfg=vae)
    engine = ContinuousBatchingEngine(p, slots=2)
    results = _drive(engine, {0: [GenerationRequest(0, seed=7, steps=3)]})
    ref = p.generate(jax.random.PRNGKey(7), batch=1, steps=3)
    assert results[0].image.shape == np.asarray(ref[0]).shape
    np.testing.assert_allclose(results[0].image, np.asarray(ref[0]),
                               atol=1e-5)


@pytest.mark.smoke
def test_zero_recompilation_after_warmup(pipe):
    """After warmup, serving any mix of steps/seeds/arrival patterns
    triggers no new XLA compilations (compile-count probe)."""
    engine = ContinuousBatchingEngine(pipe, slots=2)
    engine.warmup()
    warm = engine.compile_stats()
    assert all(v >= 1 for v in warm.values()), warm
    reqs = [GenerationRequest(i, seed=i, steps=s)
            for i, s in enumerate([2, 6, 3, 4, 5])]
    results = _drive(engine, {0: reqs[:3], 2: reqs[3:]})
    assert len(results) == 5
    assert engine.compile_stats() == warm


def test_scheduler_staggered_arrivals_all_complete_metrics_monotone(pipe):
    """More requests than slots, staggered arrivals: everything drains,
    and completed/tick/energy counters are monotone along the way."""
    engine = ContinuousBatchingEngine(pipe, slots=2)
    engine.warmup()
    reqs = [GenerationRequest(i, seed=50 + i, steps=2 + (i % 3),
                              slo_ms=1e9) for i in range(6)]
    seen, completed_series, energy_series = [], [], []
    now = 0.0
    for k in range(100):
        if k < len(reqs):
            engine.submit(reqs[k], now=now)
        seen.extend(engine.tick(now=now))
        snap = engine.metrics.snapshot(active_slots=engine.active_count,
                                      queued=len(engine.queue))
        completed_series.append(snap.completed)
        energy_series.append(snap.total_energy_j)
        now += 1.0
        if k >= len(reqs) and not engine.busy:
            break
    assert sorted(r.request_id for r in seen) == list(range(6))
    assert completed_series == sorted(completed_series)
    assert energy_series == sorted(energy_series)
    m = engine.metrics
    assert m.percentile_latency(50) <= m.percentile_latency(95)
    assert m.requests_per_s() > 0
    assert m.slo_violations == 0
    # latency bookkeeping: queue delay + service == end-to-end
    for r in seen:
        assert r.latency_s == pytest.approx(r.queue_delay_s + r.service_s)
        assert r.energy_j > 0 and r.epb_pj > 0


def test_photonic_energy_scales_with_steps(pipe):
    acct = PhotonicAccountant(TINY)
    e2, _ = acct.energy(2)
    e6, _ = acct.energy(6)
    assert e6 == pytest.approx(3 * e2, rel=1e-6)
    assert acct.energy(2, guided=True)[0] == pytest.approx(2 * e2, rel=1e-6)
    # engine results carry exactly the accountant's numbers — an fp32
    # request is billed the GPU digital baseline, not the photonic path
    e2_fp32, _ = acct.energy(2, precision='fp32')
    engine = ContinuousBatchingEngine(pipe, slots=1, photonic=acct)
    res = _drive(engine, {0: [GenerationRequest(0, seed=1, steps=2)]})
    assert res[0].energy_j == pytest.approx(e2_fp32)
    # quantized request on the same engine: the DiffLight number
    engine2 = ContinuousBatchingEngine(pipe, slots=1, photonic=acct,
                                       quality_probe=0)
    res2 = _drive(engine2, {0: [GenerationRequest(1, seed=1, steps=2,
                                                  precision='w8a8')]})
    assert res2[0].energy_j == pytest.approx(e2)
    assert res2[0].energy_j < res[0].energy_j / 100


# ---------------------------------------------------------------------------
# queue / batcher policies
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_queue_priority_then_fifo_and_depth_bound():
    q = AdmissionQueue(max_depth=3)
    lo1 = GenerationRequest(1, seed=1, priority=0)
    lo2 = GenerationRequest(2, seed=2, priority=0)
    hi = GenerationRequest(3, seed=3, priority=5)
    assert q.submit(lo1, now=0.0) and q.submit(lo2, now=1.0)
    assert q.submit(hi, now=2.0)
    assert not q.submit(GenerationRequest(4, seed=4), now=3.0)  # full
    assert q.rejected == 1
    order = [q.pop().request.request_id for _ in range(3)]
    assert order == [3, 1, 2]            # priority first, FIFO within
    assert q.pop() is None
    assert q.oldest_wait(10.0) == 0.0


def test_choose_slots_littles_law():
    # 4 req/s x (10 steps x 50ms) = 2 in flight; /0.8 util -> 3 slots
    assert choose_slots(4.0, 0.05, 10) == 3
    assert choose_slots(0.0, 0.05, 10) == 1
    assert choose_slots(1e6, 0.05, 10, max_slots=16) == 16


def test_bucket_router_routes_and_ticks(pipe):
    router = BucketRouter()
    b = router.register(ContinuousBatchingEngine(pipe, slots=1))
    assert b == bucket_for(TINY)
    assert router.submit(GenerationRequest(0, seed=3, steps=2), now=0.0)
    out = []
    for k in range(20):
        out.extend(router.tick(now=float(k)))
        if not router.busy:
            break
    assert [r.request_id for r in out] == [0]
    with pytest.raises(ValueError):
        router.register(ContinuousBatchingEngine(pipe, slots=1))


# ---------------------------------------------------------------------------
# precision policies: the quantized photonic fast path
# ---------------------------------------------------------------------------

@pytest.mark.quant
@pytest.mark.smoke
def test_w8a8_engine_matches_standalone_quant_pipeline(pipe):
    """A w8a8 request through the engine matches the standalone
    quant=True DDIM pipeline (the deprecated boolean spelling) for the
    same seed/steps.  Per-row activation scales keep batch elements
    independent, so the math is identical; the tolerance is ~1 LSB of
    the 8-bit datapath (atol 1e-3), because XLA fuses the row-scale
    reduction differently for the engine's slot-batch shape than for
    batch-1, and a ~1e-7 float difference in x/scale can flip one int8
    rounding at a tie boundary."""
    with pytest.warns(DeprecationWarning):
        qpipe = DiffusionPipeline.init(jax.random.PRNGKey(0), TINY,
                                       quant=True)
    engine = ContinuousBatchingEngine(pipe, slots=2, quality_probe=0)
    reqs = [GenerationRequest(i, seed=40 + i, steps=s, precision='w8a8')
            for i, s in enumerate([3, 5, 2])]
    results = _drive(engine, {0: reqs[:2], 2: [reqs[2]]})
    assert sorted(r.request_id for r in results) == [0, 1, 2]
    for r in results:
        ref = qpipe.generate(jax.random.PRNGKey(40 + r.request_id),
                             batch=1, steps=r.steps)
        np.testing.assert_allclose(r.image, np.asarray(ref[0]), atol=1e-3)
        # and it ran the quant path, not fp32: strictly closer to the
        # quantized reference than to the fp32 one
        fp = pipe.generate(jax.random.PRNGKey(40 + r.request_id),
                           batch=1, steps=r.steps)
        d_quant = float(np.max(np.abs(r.image - np.asarray(ref[0]))))
        d_fp32 = float(np.max(np.abs(r.image - np.asarray(fp[0]))))
        assert d_quant < d_fp32
        assert r.precision == 'w8a8' and r.policy.quantized


@pytest.mark.quant
def test_mixed_precision_ticks_zero_recompiles(pipe):
    """One engine serving fp32 + w8a8 + w8a8+noise side by side: per-tick
    precision grouping keeps every step call on a pre-compiled function —
    compile stats are frozen after one warmup per policy."""
    engine = ContinuousBatchingEngine(pipe, slots=3, quality_probe=0)
    engine.warmup(precisions=('fp32', 'w8a8', 'w8a8+noise'))
    warm = engine.compile_stats()
    assert warm['step_fp32'] == 1
    assert warm['step_w8a8'] == 1
    assert warm['step_w8a8_noise'] == 1
    mix = ['fp32', 'w8a8', 'w8a8+noise']
    reqs = [GenerationRequest(i, seed=60 + i, steps=2 + (i % 3),
                              precision=mix[i % 3]) for i in range(6)]
    results = _drive(engine, {0: reqs[:4], 2: reqs[4:]})
    assert sorted(r.request_id for r in results) == list(range(6))
    assert engine.compile_stats() == warm
    # each request still matches its own standalone trajectory (fp32 at
    # float precision; w8a8 to ~1 LSB — see the equivalence test above)
    for r in results:
        if r.precision == 'w8a8+noise':
            continue
        ref = pipe.generate(jax.random.PRNGKey(60 + r.request_id), batch=1,
                            steps=r.steps,
                            policy=PrecisionPolicy.from_name(r.precision))
        atol = 1e-5 if r.precision == 'fp32' else 1e-3
        np.testing.assert_allclose(r.image, np.asarray(ref[0]), atol=atol)


@pytest.mark.quant
def test_frontier_reports_accuracy_vs_epb(pipe):
    """snapshot().frontier: quantized requests sit ~2 orders of magnitude
    below fp32 in EPB and carry a PSNR/MSE quality probe vs the fp32
    reference; fp32 requests ARE the reference (no probe)."""
    engine = ContinuousBatchingEngine(pipe, slots=2)
    engine.warmup(precisions=('fp32', 'w8a8'))
    reqs = [GenerationRequest(0, seed=5, steps=3, precision='fp32'),
            GenerationRequest(1, seed=5, steps=3, precision='w8a8')]
    results = _drive(engine, {0: reqs})
    by_id = {r.request_id: r for r in results}
    assert by_id[0].quality_mse is None
    assert by_id[1].quality_mse is not None and by_id[1].quality_mse >= 0
    assert by_id[1].quality_psnr_db > 20          # tracks fp32 closely
    snap = engine.metrics.snapshot()
    f = snap.frontier
    assert set(f) == {'fp32', 'w8a8'}
    assert f['w8a8']['mean_epb_pj'] < f['fp32']['mean_epb_pj'] / 50
    assert f['w8a8']['probed'] == 1
    assert np.isnan(f['fp32']['mean_psnr_db'])
    # per-request frontier points mirror the results
    pts = {p.request_id: p for p in engine.metrics.frontier_points}
    assert pts[1].psnr_db == by_id[1].quality_psnr_db
    assert pts[0].epb_pj == by_id[0].epb_pj


@pytest.mark.quant
def test_noisy_engine_deterministic_under_seed(pipe):
    """w8a8+noise serving is reproducible: identical engines and request
    sequences produce bit-identical images; a different noise seed does
    not."""
    def run(noise_seed):
        e = ContinuousBatchingEngine(pipe, slots=2, quality_probe=0,
                                     noise_seed=noise_seed)
        reqs = [GenerationRequest(i, seed=70 + i, steps=3,
                                  precision='w8a8+noise') for i in range(2)]
        return {r.request_id: r.image for r in _drive(e, {0: reqs})}

    a, b, c = run(0), run(0), run(1)
    for i in a:
        np.testing.assert_array_equal(a[i], b[i])
    assert any(np.any(a[i] != c[i]) for i in a)


@pytest.mark.quant
@pytest.mark.smoke
def test_request_precision_validation():
    with pytest.raises(ValueError, match='precision'):
        GenerationRequest(0, seed=1, precision='int4')
    with pytest.raises(ValueError, match='precision'):
        GenerationRequest(0, seed=1, precision='W8A8')   # case-sensitive
    assert GenerationRequest(0, seed=1,
                             precision='w8a8+noise').precision == 'w8a8+noise'


def test_group_by_precision_masks():
    groups = group_by_precision(['fp32', None, 'w8a8', 'fp32', None])
    assert set(groups) == {'fp32', 'w8a8'}
    np.testing.assert_array_equal(groups['fp32'],
                                  [True, False, False, True, False])
    np.testing.assert_array_equal(groups['w8a8'],
                                  [False, False, True, False, False])
    assert group_by_precision([None, None]) == {}


def test_choose_slots_per_precision_mapping():
    # per-precision load terms add across one shared slot buffer:
    # fp32 1 req/s x 10 x 0.1s = 1.0; w8a8 4 req/s x 10 x 0.025s = 1.0
    n = choose_slots({'fp32': 1.0, 'w8a8': 4.0},
                     {'fp32': 0.1, 'w8a8': 0.025}, 10)
    assert n == 3                                 # ceil(2.0 / 0.8)
    # scalar step time broadcast over the mapping
    assert choose_slots({'fp32': 2.0, 'w8a8': 2.0}, 0.05, 10) == 3
    assert choose_slots({'fp32': 0.0}, 0.05, 10) == 1


# ---------------------------------------------------------------------------
# weights as arguments; serve's model setup
# ---------------------------------------------------------------------------

def test_steps_take_weights_as_arguments(pipe):
    """No step variant or decode compiles a weight in as a constant: every
    lowered program's constants are smaller than the model's weights."""
    import re
    vae = VAEConfig(img_size=16, in_ch=3, z_ch=3, base_ch=16,
                    ch_mults=(1, 2), groups=8)
    p = DiffusionPipeline(TINY, pipe.unet_params, pipe.sched, vae,
                          jax.tree.map(jnp.asarray, DiffusionPipeline.init(
                              jax.random.PRNGKey(1), TINY, vae).vae_params))
    engine = ContinuousBatchingEngine(p, slots=2, quality_probe=0)
    weights = jax.tree_util.tree_leaves((p.unet_params, p.vae_params))
    smallest = min(int(w.size) for w in weights if w.ndim >= 2)
    info = engine.aot_warmup(precisions=('fp32', 'w8a8'))
    assert {'step_fp32', 'step_w8a8', 'vae_decode'} <= set(info['compiled'])
    for label, exe in info['compiled'].items():
        for dims in re.findall(r'= f32\[([\d,]+)\]\S* constant\(',
                               exe.as_text()):
            n = int(np.prod([int(d) for d in dims.split(',')]))
            assert n < smallest, (label, dims)


def test_probe_reference_applies_conditioning_to_unguided_requests():
    """An unguided step on a conditioned engine predicts the conditional
    noise, so the probe's fp32 reference must be conditioned too."""
    cfg = UNetConfig('tiny-sdm-probe', img_size=16, in_ch=3, base_ch=32,
                     ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(8,),
                     n_heads=4, timesteps=16, context_dim=8)
    p = DiffusionPipeline.init(jax.random.PRNGKey(0), cfg)
    ctx = jnp.tile(jax.random.normal(jax.random.PRNGKey(9), (1, 4, 8)),
                   (2, 1, 1))
    engine = ContinuousBatchingEngine(p, slots=2, context=ctx)
    req = GenerationRequest(0, seed=21, steps=3, precision='w8a8')
    results = _drive(engine, {0: [req]})
    served_fp32 = p.generate(jax.random.PRNGKey(21), batch=1, steps=3,
                             context=ctx[:1])
    np.testing.assert_allclose(engine._fp32_reference(req, guided=False),
                               np.asarray(served_fp32[0]), atol=1e-6)
    assert results[0].quality_psnr_db > 40.0


@pytest.mark.parametrize('model', ['ddpm_cifar10', 'ldm_churches',
                                   'ldm_beds'])
def test_serve_refuses_models_that_do_not_trace(model):
    from repro.launch.serve import build_pipeline
    with pytest.raises(ValueError, match='does not trace'):
        build_pipeline(model)


def test_serve_context_is_one_seeded_row_per_slot():
    from repro.configs.diffusion import CONTEXT_TOKENS
    from repro.launch.serve import build_context
    cfg = UNetConfig('tiny-ctx', img_size=8, in_ch=4, base_ch=32,
                     ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(4,),
                     n_heads=4, timesteps=16, context_dim=8)
    p = DiffusionPipeline.init(jax.random.PRNGKey(0), cfg)
    ctx = np.asarray(build_context(p, slots=3, seed=5))
    assert ctx.shape == (3, CONTEXT_TOKENS, 8)
    np.testing.assert_array_equal(ctx[0], ctx[2])
    np.testing.assert_array_equal(ctx, np.asarray(build_context(p, 3, 5)))
    assert build_context(DiffusionPipeline.init(jax.random.PRNGKey(0), TINY),
                         slots=3) is None


def test_poisson_trace_covers_every_precision_and_guidance_pair():
    from repro.launch.serve import poisson_trace
    trace = poisson_trace(6, 10.0, 4, precision=('fp32', 'w8a8'),
                          guidance=7.5)
    pairs = [(r.precision, r.guidance) for r in trace]
    assert pairs[:4] == [('fp32', 0.0), ('fp32', 7.5), ('w8a8', 0.0),
                         ('w8a8', 7.5)]
    assert all(r.precision == 'fp32' for r in poisson_trace(3, 10.0, 4))
