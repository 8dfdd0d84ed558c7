"""Cache- and convergence-aware scheduler: DeepCache-phased slots plus
speculative early-exit draining (``repro.serving.engine``)."""
import jax
import numpy as np
import pytest

from repro.models.unet import UNetConfig
from repro.diffusion.pipeline import DiffusionPipeline
from repro.serving import (ContinuousBatchingEngine, GenerationRequest,
                           AdmissionQueue, PhotonicAccountant,
                           split_cache_phase)

TINY = UNetConfig('tiny-cache-serve', img_size=8, in_ch=1, base_ch=8,
                  ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(4,),
                  n_heads=2, timesteps=32, groups=4)


@pytest.fixture(scope='module')
def pipe():
    return DiffusionPipeline.init(jax.random.PRNGKey(0), TINY)


def _req(i, steps=7, **kw):
    return GenerationRequest(request_id=i, seed=100 + i, steps=steps, **kw)


@pytest.mark.sched
@pytest.mark.smoke
def test_cached_engine_zero_recompiles_and_phase(pipe):
    """Warmup pre-compiles exactly the (refresh, skip) step pair; a full
    serve touches nothing else, every skip tick is whole-batch (phase
    alignment), and per-request eval counts follow the cadence."""
    eng = ContinuousBatchingEngine(pipe, slots=4, cache_interval=3,
                                   quality_probe=0)
    eng.warmup()
    warm = eng.compile_stats()
    assert warm['step_fp32_refresh'] == 1
    assert warm['step_fp32_skip'] == 1
    for i in range(5):
        eng.submit(_req(i, steps=7), now=0.0)
    results = eng.run_until_idle(now=0.0)
    assert len(results) == 5
    assert eng.compile_stats() == warm, 'recompiled mid-serve'
    for r in results:
        # interval 3, admitted at phase 0: refresh at ticks 0, 3, 6
        assert r.full_evals == 3
        assert r.cached_evals == 4
        assert r.steps_executed == 7
        assert not r.early_exit
        assert np.all(np.isfinite(r.image))
    snap = eng.metrics.snapshot()
    assert snap.mixed_ticks == 0          # every tick whole-batch
    assert snap.cached_steps == 5 * 4
    assert snap.full_steps == 5 * 3
    assert 0.5 < snap.cache_hit_rate < 0.6   # 20 / 35


@pytest.mark.sched
@pytest.mark.smoke
def test_opt_out_matches_plain_engine(pipe):
    """A request that opts out (cache_interval=1) rides the refresh path
    every tick — its output must match the plain full-step engine."""
    eng_plain = ContinuousBatchingEngine(pipe, slots=2, quality_probe=0)
    eng_cache = ContinuousBatchingEngine(pipe, slots=2, cache_interval=3,
                                         quality_probe=0)
    for eng in (eng_plain, eng_cache):
        eng.warmup()
    out = {}
    for name, eng in (('plain', eng_plain), ('cache', eng_cache)):
        eng.submit(_req(0, steps=5, cache_interval=1), now=0.0)
        out[name] = eng.run_until_idle(now=0.0)[0]
    assert out['cache'].cached_evals == 0
    assert out['cache'].full_evals == 5
    np.testing.assert_allclose(out['cache'].image, out['plain'].image,
                               atol=1e-5, rtol=0)
    # opted-out slots produce mixed ticks when cached slots coexist;
    # alone they don't
    assert eng_cache.metrics.snapshot().mixed_ticks == 0


@pytest.mark.sched
def test_phase_aligned_admission_mid_flight(pipe):
    """A request arriving mid-cadence is held until the next refresh tick
    so the shared cadence never fragments (mixed_ticks stays 0)."""
    eng = ContinuousBatchingEngine(pipe, slots=4, cache_interval=3,
                                   quality_probe=0)
    eng.warmup()
    eng.submit(_req(0, steps=7), now=0.0)
    done = []
    done += eng.tick(now=0.0)      # phase 0 -> 1
    done += eng.tick(now=0.0)      # phase 1 -> 2: mid-cadence
    eng.submit(_req(1, steps=7), now=0.0)
    done += eng.tick(now=0.0)      # phase 2: admission held
    assert sum(a is not None for a in eng._slot) == 1
    done += eng.tick(now=0.0)      # phase 0: admitted on the refresh tick
    assert sum(a is not None for a in eng._slot) == 2
    while eng.busy:
        done += eng.tick(now=0.0)
    assert len(done) == 2
    assert eng.metrics.snapshot().mixed_ticks == 0
    for r in done:
        assert r.full_evals == 3 and r.cached_evals == 4


@pytest.mark.sched
@pytest.mark.smoke
def test_early_exit_drains_and_saves_steps(pipe):
    """With a huge tolerance every request converges immediately: it
    drains after exit_min_steps with the converged x0 committed, the
    steps-saved histogram fills, and the energy bill shrinks."""
    eng = ContinuousBatchingEngine(pipe, slots=2, exit_tol=1e9,
                                   exit_patience=1, quality_probe=0)
    eng.warmup()
    eng.submit(_req(0, steps=12), now=0.0)
    r = eng.run_until_idle(now=0.0)[0]
    assert r.early_exit
    assert r.steps_executed == eng.exit_min_steps
    assert r.steps_saved == 12 - eng.exit_min_steps
    snap = eng.metrics.snapshot()
    assert snap.early_exits == 1
    assert snap.steps_saved == r.steps_saved
    assert snap.steps_saved_hist.get(r.steps_saved) == 1
    # full-run comparison: same request, exit disabled
    eng2 = ContinuousBatchingEngine(pipe, slots=2, quality_probe=0)
    eng2.warmup()
    eng2.submit(_req(0, steps=12), now=0.0)
    r2 = eng2.run_until_idle(now=0.0)[0]
    assert not r2.early_exit and r2.steps_executed == 12
    assert r.energy_j < r2.energy_j


@pytest.mark.sched
@pytest.mark.smoke
def test_exit_tol_zero_disables_early_exit(pipe):
    eng = ContinuousBatchingEngine(pipe, slots=1, exit_tol=1e9,
                                   exit_patience=1, quality_probe=0)
    eng.warmup()
    eng.submit(_req(0, steps=6, exit_tol=0.0), now=0.0)  # per-request off
    r = eng.run_until_idle(now=0.0)[0]
    assert not r.early_exit and r.steps_executed == 6


@pytest.mark.sched
@pytest.mark.smoke
def test_skip_ticks_billed_shallow():
    """Skip ticks are billed through the DeepCache workload transform:
    cheaper than full ticks, dearer than free."""
    acct = PhotonicAccountant(TINY)
    assert 0.0 < acct.shallow_fraction < 1.0
    full, _ = acct.energy(5, precision='w8a8')
    mixed, _ = acct.energy_evals(1, 4, precision='w8a8')
    floor, _ = acct.energy_evals(1, 0, precision='w8a8')
    assert floor < mixed < full
    # no skips -> identical to the step-count bill (same simulate point)
    e_steps = acct.energy(3, precision='fp32')
    e_evals = acct.energy_evals(3, 0, precision='fp32')
    assert e_steps == e_evals


@pytest.mark.sched
@pytest.mark.smoke
def test_shed_surfaced_in_metrics(pipe):
    """A bounded admission queue sheds overload; the shed count surfaces
    in the metrics snapshot and summary."""
    eng = ContinuousBatchingEngine(pipe, slots=1,
                                   queue=AdmissionQueue(max_depth=2),
                                   quality_probe=0)
    accepted = [eng.submit(_req(i, steps=2), now=0.0) for i in range(5)]
    assert accepted == [True, True, False, False, False]
    assert eng.metrics.snapshot().shed == 3
    assert eng.metrics.summary()['shed'] == 3
    eng.warmup()
    assert len(eng.run_until_idle(now=0.0)) == 2


@pytest.mark.sched
def test_guided_and_quantized_cached_paths(pipe):
    """Caching composes with guidance (two cache buffers) and with the
    w8a8 precision policy (per-policy refresh/skip pairs), still with
    zero recompiles after warmup."""
    ctx = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 16))
    cfg = UNetConfig('tiny-cache-guided', img_size=8, in_ch=1, base_ch=8,
                     ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(4,),
                     n_heads=2, timesteps=32, groups=4, context_dim=16)
    gpipe = DiffusionPipeline.init(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(gpipe, slots=2, context=ctx,
                                   cache_interval=2, quality_probe=0)
    eng.warmup(precisions=('fp32', 'w8a8'))
    warm = eng.compile_stats()
    for label in ('step_fp32_refresh', 'step_fp32_skip',
                  'step_fp32_refresh_guided', 'step_fp32_skip_guided',
                  'step_w8a8_refresh', 'step_w8a8_skip'):
        assert warm[label] == 1, label
    eng.submit(_req(0, steps=5, guidance=2.0), now=0.0)
    eng.submit(_req(1, steps=5, precision='w8a8'), now=0.0)
    results = eng.run_until_idle(now=0.0)
    assert len(results) == 2
    assert eng.compile_stats() == warm
    for r in results:
        assert r.cached_evals > 0
        assert np.all(np.isfinite(r.image))


@pytest.mark.sched
@pytest.mark.smoke
def test_split_cache_phase():
    mask = np.array([True, True, False, True])
    refresh = np.array([True, False, True, False])
    r, s = split_cache_phase(mask, refresh)
    assert r.tolist() == [True, False, False, False]
    assert s.tolist() == [False, True, False, True]
    assert not np.any(r & s)
    assert ((r | s) == mask).all()


@pytest.mark.sched
def test_frontier_reports_scheduler_columns(pipe):
    """The per-policy frontier carries the quality-vs-throughput axes:
    executed vs requested steps, cache hit rate and early exits."""
    eng = ContinuousBatchingEngine(pipe, slots=2, cache_interval=3,
                                   exit_tol=1e9, exit_patience=1,
                                   quality_probe=1)
    eng.warmup()
    eng.submit(_req(0, steps=6), now=0.0)
    r = eng.run_until_idle(now=0.0)[0]
    f = eng.metrics.frontier()['fp32']
    assert f['mean_steps_requested'] == 6.0
    assert f['mean_steps_executed'] == float(r.steps_executed)
    assert f['mean_steps_saved'] == float(r.steps_saved)
    assert f['early_exits'] == 1
    # the cached/early-exited fp32 request is probe-eligible
    assert r.quality_mse is not None
