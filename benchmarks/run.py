"""Benchmark harness — one section per paper table/figure.

Prints ``name,value,unit`` CSV rows (the BENCH_*.json schema: each row
is ``{name, value, unit}``; value is numeric wherever the quantity is,
unit is the physical/logical unit string):
  * Table I   — model parameter counts + W8A8 quality proxy
  * Fig. 8    — energy ablation (baseline vs S/W-opt vs pipelined vs
                DAC-sharing vs combined), per DM
  * Fig. 9    — GOPS vs CPU/GPU/DeepCache/FPGA1/FPGA2/PACE
  * Fig. 10   — EPB vs the same baselines
  * DSE       — paper config percentile in the budget-constrained sweep
  * kernels   — wall-time microbenches of the three Pallas kernel oracles
                (CPU) + sparse-vs-dense transposed conv
  * serving   — continuous-batching engine vs naive batch-at-once under a
                staggered arrival trace (requests/s + per-request energy)
  * quant_serving — the precision-policy fast path: the same trace served
                at fp32 vs w8a8 (requests/s, EPB, PSNR quality probe) plus
                a mixed-precision zero-recompile check
  * cache_serving — the cache- and convergence-aware scheduler: the same
                Poisson trace served by the full-step engine vs the
                DeepCache-phased + early-exit engine (requests/s speedup,
                PSNR vs the full-step fp32 reference, per-request energy
                with skip ticks billed at the shallow workload fraction)
  * coldstart — time-to-first-tick across REAL process restarts: a cold
                subprocess (empty persistent compilation cache) vs a warm
                one (same cache dir, second run) — the restart recompile
                storm vs the cache load
  * overload  — a 5x-overload Poisson trace against a bounded
                deadline-aware queue: shed rate by cause, p99 queue wait,
                peak queue depth (the survival proof)
  * sharded_serving — the slot-sharded engine at 1/2/4/8 simulated
                devices: device-parallel requests/s modeled from
                measured per-device tick times (the host simulation
                serializes devices, so wall clock is emitted separately
                as the audit trail), plus decode overlap on/off at 8
                devices and a zero-recompile check
  * obs_overhead — the observability tax: the same request batch served
                with tracing off (NULL_TRACER) vs on (a live Tracer
                recording every span); asserts the traced requests/s is
                within 5% of untraced (best-of-3 each, so scheduler
                noise does not fail the gate) and reports the per-run
                event volume

Rows persist to ``BENCH_PR10.json`` at the repo root (NaN/inf values
are sanitized to null — the file is strict JSON).  Older
``BENCH_PR*.json`` files used ``{name, us_per_call, derived}`` rows;
``load_bench`` reads both shapes.

Regression gate: by default a >10% drop of ``serving/engine_rps`` vs
the newest prior ``BENCH_PR*.json`` only WARNS on stderr.  With
``--check`` the run becomes a merge gate — it compares against the
newest *committed* bench file (including this PR's), exits nonzero on
regression, and does not persist rows.  ``BENCH_TOL`` (fraction,
default 0.10) loosens the gate for slower CI hardware.

Run everything (default) or name sections on argv:
    PYTHONPATH=src python benchmarks/run.py cache_serving
    PYTHONPATH=src python benchmarks/run.py serving --check   # CI gate
"""
import glob
import json
import os
import re
import subprocess
import sys
import tempfile
import time

import numpy as np


def _timeit(fn, iters=5):
    fn()                                   # compile / warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    try:
        import jax
        jax.block_until_ready(out)
    except Exception:
        pass
    return (time.perf_counter() - t0) / iters * 1e6


def bench_table1(emit):
    import jax
    from repro.configs.diffusion import PAPER_MODELS, PAPER_PARAM_COUNTS
    from repro.models.unet import init_unet
    for name, cfg in PAPER_MODELS.items():
        shapes = jax.eval_shape(lambda c=cfg: init_unet(
            jax.random.PRNGKey(0), c))
        n = sum(int(np.prod(s.shape)) for s in
                jax.tree_util.tree_leaves(shapes))
        emit(f'table1/{name}/params', round(n / 1e6, 2), 'Mparams')
        emit(f'table1/{name}/paper_params',
             round(PAPER_PARAM_COUNTS[name], 2), 'Mparams')


def _workloads():
    from repro.configs.diffusion import PAPER_MODELS
    from repro.core.photonic.workload import unet_workload
    return {n: unet_workload(c, ctx_len=77 if c.context_dim else None)
            for n, c in PAPER_MODELS.items()}


def bench_fig8(emit):
    from repro.core.photonic.simulator import ablation
    ratios = []
    for name, w in _workloads().items():
        ab = ablation(w)
        base = ab['baseline'].energy_j
        for k, r in ab.items():
            emit(f'fig8/{name}/{k}/norm_energy',
                 round(r.energy_j / base, 4), 'ratio')
        ratios.append(base / ab['combined'].energy_j)
    emit('fig8/avg_combined_reduction', round(float(np.mean(ratios)), 2),
         'x')


def bench_fig9_fig10(emit):
    from repro.core.photonic.arch import PAPER_OPTIMUM
    from repro.core.photonic.baselines import derive_baselines
    from repro.core.photonic.simulator import simulate
    ws = _workloads()
    reps = {n: simulate(w, PAPER_OPTIMUM) for n, w in ws.items()}
    for n, r in reps.items():
        emit(f'fig9/{n}/difflight_throughput', round(r.gops, 1), 'GOPS')
        emit(f'fig10/{n}/difflight_epb', round(r.epb_pj, 4), 'pJ/bit')
    gops = float(np.mean([r.gops for r in reps.values()]))
    epb = float(np.mean([r.epb_pj for r in reps.values()]))
    for name, b in derive_baselines(gops, epb).items():
        key = name.split(' ')[0].lower().replace('_', '')
        emit(f'fig9/baseline/{key}_throughput', round(b.gops, 2), 'GOPS')
        emit(f'fig10/baseline/{key}_epb', round(b.epb_pj, 4), 'pJ/bit')
        emit(f'fig9/improvement/{key}', round(gops / b.gops, 2), 'x')
        emit(f'fig10/improvement/{key}', round(b.epb_pj / epb, 2), 'x')


def bench_deepcache(emit):
    """Derived (not anchored) DeepCache comparison point: our DeepCache
    implementation's MAC factor -> throughput/energy point on the same
    simulator, vs the paper's anchored 192x GOPS / 376x EPB ratios."""
    from repro.configs.diffusion import PAPER_MODELS
    from repro.core.photonic.arch import PAPER_OPTIMUM
    from repro.core.photonic.simulator import simulate
    from repro.core.photonic.workload import unet_workload
    from repro.diffusion.deepcache import deepcache_workload_factor
    for name, cfg in PAPER_MODELS.items():
        f = deepcache_workload_factor(cfg, interval=5)
        emit(f'deepcache/{name}/mac_factor', round(f, 3), 'ratio')
    # DiffLight running the DeepCache-reduced workload: compounding check
    w = unet_workload(PAPER_MODELS['ddpm_cifar10'])
    f = deepcache_workload_factor(PAPER_MODELS['ddpm_cifar10'], 5)
    r_full = simulate(w, PAPER_OPTIMUM)
    r_dc = simulate(w.scale(f), PAPER_OPTIMUM)
    emit('deepcache/difflight_compound_energy',
         round(r_full.energy_j / r_dc.energy_j, 2), 'x')


def bench_dse(emit):
    from repro.configs.diffusion import PAPER_MODELS
    from repro.core.photonic.arch import PAPER_OPTIMUM, dse_space
    from repro.core.photonic.simulator import dse_score
    from repro.core.photonic.workload import unet_workload
    w = unet_workload(PAPER_MODELS['sd_v1_4'], ctx_len=77)

    def mr_count(c):
        return (c.Y * 2 * c.K * c.N + c.H * (4 * c.M * c.L + 3 * c.M * c.N)
                + 2 * c.M * c.L)
    budget = 1.1 * mr_count(PAPER_OPTIMUM)
    t0 = time.perf_counter()
    scored = [(dse_score(w, c), c) for c in dse_space()
              if mr_count(c) <= budget]
    dt = (time.perf_counter() - t0) * 1e6
    scored.sort(key=lambda x: -x[0])
    mine = dse_score(w, PAPER_OPTIMUM)
    pct = float(np.searchsorted(-np.asarray([s for s, _ in scored]),
                                -mine)) / len(scored)
    best = scored[0][1]
    emit('dse/n_configs', len(scored), 'configs')
    emit('dse/sweep_time', round(dt, 1), 'us')
    emit('dse/paper_config_percentile', round(pct, 3), 'fraction')
    emit('dse/our_optimum',
         f'[{best.Y} {best.N} {best.K} {best.H} {best.L} {best.M}]',
         'config')


def bench_kernels(emit):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)
    f32 = jax.jit(lambda: x @ w)
    q = jax.jit(lambda: ops.w8a8_matmul(x, w, mode='xla'))
    emit('kernels/matmul_f32', round(_timeit(f32), 1), 'us')
    emit('kernels/w8a8_matmul_xla', round(_timeit(q), 1), 'us')
    qq = jnp.asarray(rng.normal(size=(2, 4, 128, 64)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(2, 4, 256, 64)), jnp.float32)
    fa = jax.jit(lambda: ops.flash_attention(qq, kk, kk, mode='xla'))
    emit('kernels/flash_attention_xla', round(_timeit(fa), 1), 'us')
    img = jnp.asarray(rng.normal(size=(2, 32, 32, 64)), jnp.float32)
    sc = jnp.ones((64,))
    gs = jax.jit(lambda: ops.fused_gn_swish(img, sc, sc, mode='xla'))
    emit('kernels/fused_gn_swish_xla', round(_timeit(gs), 1), 'us')
    # C4: sparse vs dense transposed conv wall time (CPU)
    from repro.core import sparse_dataflow as SD
    xc = jnp.asarray(rng.normal(size=(2, 32, 32, 64)), jnp.float32)
    ker = jnp.asarray(rng.normal(size=(4, 4, 64, 64)), jnp.float32)
    dense = jax.jit(lambda: SD.conv_transpose_dense(xc, ker, 2))
    sparse = jax.jit(lambda: SD.conv_transpose_sparse(xc, ker, 2))
    td, ts = _timeit(dense), _timeit(sparse)
    emit('kernels/convt_dense', round(td, 1), 'us')
    emit('kernels/convt_sparse', round(ts, 1), 'us')
    emit('kernels/convt_sparse_speedup', round(td / max(ts, 1e-9), 2), 'x')


def bench_serving(emit):
    """Continuous batching vs batch-at-once under staggered arrivals with
    heterogeneous step counts (the serving reality: users ask for
    different quality/step budgets).

    Batch-at-once can only launch once the LAST request has arrived, and
    its fixed-shape sampler must run the WHOLE batch for max(steps); the
    engine starts at the first arrival, gives each slot its own step
    trajectory, and refills a slot the moment a short request drains."""
    import jax
    from repro.diffusion.pipeline import DiffusionPipeline
    from repro.models.unet import UNetConfig
    from repro.serving import ContinuousBatchingEngine, GenerationRequest
    cfg = UNetConfig('bench-serve', img_size=16, in_ch=3, base_ch=32,
                     ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(8,),
                     n_heads=4, timesteps=50)
    pipe = DiffusionPipeline.init(jax.random.PRNGKey(0), cfg)
    N, slots = 8, 4
    step_counts = [3 + (3 * i) % 8 for i in range(N)]        # 3..10, mixed
    max_steps = max(step_counts)
    gen = jax.jit(lambda k: pipe.generate(k, batch=N, steps=max_steps))
    jax.block_until_ready(gen(jax.random.PRNGKey(1)))       # compile
    t0 = time.perf_counter()
    jax.block_until_ready(gen(jax.random.PRNGKey(2)))
    t_batch = time.perf_counter() - t0

    engine = ContinuousBatchingEngine(pipe, slots=slots)
    engine.warmup()
    # requests staggered across one batch-service window
    trace = [GenerationRequest(request_id=i, seed=100 + i,
                               steps=step_counts[i],
                               arrival_time=i * t_batch / N)
             for i in range(N)]
    warm = engine.compile_stats()
    t0 = time.perf_counter()
    results = engine.replay(trace)
    makespan = time.perf_counter() - t0
    assert len(results) == N
    assert engine.compile_stats() == warm, 'engine recompiled mid-serve'

    base_makespan = trace[-1].arrival_time + t_batch
    base_rps = N / base_makespan
    eng_rps = N / makespan
    s = engine.metrics.summary()
    emit('serving/batch_at_once_rps', round(base_rps, 3), 'req/s')
    emit('serving/engine_rps', round(eng_rps, 3), 'req/s')
    emit('serving/speedup', round(eng_rps / base_rps, 2), 'x')
    emit('serving/p50_latency', round(s['p50_latency_ms'], 1), 'ms')
    emit('serving/p95_latency', round(s['p95_latency_ms'], 1), 'ms')
    emit('serving/energy_per_request',
         round(s['energy_per_request_mj'], 3), 'mJ')


def bench_quant_serving(emit):
    """fp32 vs w8a8 serving on the SAME trace: the precision-policy fast
    path's headline numbers — requests/s, per-request energy/EPB (fp32 is
    billed the GPU digital baseline, w8a8 the DiffLight simulation), the
    PSNR quality probe, and a mixed-precision zero-recompile check."""
    import jax
    from repro.diffusion.pipeline import DiffusionPipeline
    from repro.models.unet import UNetConfig
    from repro.serving import ContinuousBatchingEngine, GenerationRequest
    cfg = UNetConfig('bench-qserve', img_size=16, in_ch=3, base_ch=32,
                     ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(8,),
                     n_heads=4, timesteps=50)
    pipe = DiffusionPipeline.init(jax.random.PRNGKey(0), cfg)
    N, slots = 6, 3
    step_counts = [3 + (2 * i) % 5 for i in range(N)]        # 3..7, mixed

    def serve(precision, n=N, quality_probe=0):
        # probe off while timing: the eager fp32 reference is measurement
        # apparatus, not served work
        engine = ContinuousBatchingEngine(pipe, slots=slots,
                                          quality_probe=quality_probe)
        engine.warmup(precisions=(precision,))
        for i in range(n):
            engine.submit(GenerationRequest(
                request_id=i, seed=100 + i, steps=step_counts[i % N],
                precision=precision), now=0.0)
        warm = engine.compile_stats()
        t0 = time.perf_counter()
        results = engine.run_until_idle(now=0.0, tick_dt=0.01)
        makespan = time.perf_counter() - t0
        assert len(results) == n
        assert engine.compile_stats() == warm, 'recompiled mid-serve'
        f = engine.metrics.frontier()[precision]
        return n / makespan, f

    fp32_rps, fp32_f = serve('fp32')
    w8a8_rps, w8a8_f = serve('w8a8')
    _, w8a8_q = serve('w8a8', n=2, quality_probe=1)    # quality pass
    emit('quant_serving/fp32_rps', round(fp32_rps, 3), 'req/s')
    emit('quant_serving/w8a8_rps', round(w8a8_rps, 3), 'req/s')
    emit('quant_serving/fp32_epb', round(fp32_f['mean_epb_pj'], 4),
         'pJ/bit')
    emit('quant_serving/w8a8_epb', round(w8a8_f['mean_epb_pj'], 4),
         'pJ/bit')
    emit('quant_serving/fp32_energy_per_req',
         round(fp32_f['mean_energy_j'] * 1e3, 4), 'mJ')
    emit('quant_serving/w8a8_energy_per_req',
         round(w8a8_f['mean_energy_j'] * 1e3, 4), 'mJ')
    emit('quant_serving/epb_improvement',
         round(fp32_f['mean_epb_pj'] / w8a8_f['mean_epb_pj'], 2), 'x')
    emit('quant_serving/w8a8_psnr_vs_fp32',
         round(w8a8_q['mean_psnr_db'], 2), 'dB')
    emit('quant_serving/w8a8_mse_vs_fp32',
         float(f"{w8a8_q['mean_mse']:.3e}"), 'mse')

    # mixed-precision tick: every policy in one engine, zero recompiles
    engine = ContinuousBatchingEngine(pipe, slots=slots, quality_probe=0)
    engine.warmup(precisions=('fp32', 'w8a8', 'w8a8+noise'))
    warm = engine.compile_stats()
    mix = ['fp32', 'w8a8', 'w8a8+noise']
    for i in range(N):
        engine.submit(GenerationRequest(
            request_id=100 + i, seed=200 + i, steps=step_counts[i],
            precision=mix[i % 3]), now=0.0)
    results = engine.run_until_idle(now=0.0, tick_dt=0.01)
    assert len(results) == N
    ok = engine.compile_stats() == warm
    emit('quant_serving/mixed_zero_recompiles', int(ok), 'bool')


def bench_cache_serving(emit):
    """The cache- and convergence-aware scheduler's headline numbers:
    the SAME Poisson trace served by (a) the PR6-style full-step engine
    and (b) the DeepCache-phased engine with speculative early exit.

    Reports the requests/s speedup, PSNR of the scheduled outputs vs the
    full-step fp32 reference (quality probe), the per-request energy with
    skip ticks billed at the shallow workload fraction of a full UNet
    pass, and a zero-recompile check on the cached engine (the refresh /
    skip pair is pre-compiled at warmup)."""
    import jax
    from repro.diffusion.pipeline import DiffusionPipeline
    from repro.models.unet import UNetConfig
    from repro.serving import ContinuousBatchingEngine, GenerationRequest
    cfg = UNetConfig('bench-cserve', img_size=16, in_ch=3, base_ch=32,
                     ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(8,),
                     n_heads=4, timesteps=50)
    pipe = DiffusionPipeline.init(jax.random.PRNGKey(0), cfg)
    N, slots, steps = 8, 4, 12
    interval, exit_tol, patience = 3, 0.005, 2
    rng = np.random.default_rng(7)
    arrivals = np.cumsum(rng.exponential(0.02, N))      # same Poisson trace

    def trace():
        return [GenerationRequest(request_id=i, seed=100 + i, steps=steps,
                                  arrival_time=float(arrivals[i]))
                for i in range(N)]

    def serve(n=N, quality_probe=0, **knobs):
        engine = ContinuousBatchingEngine(pipe, slots=slots,
                                          quality_probe=quality_probe,
                                          **knobs)
        engine.warmup()
        for req in trace()[:n]:
            engine.submit(req, now=0.0)
        warm = engine.compile_stats()
        t0 = time.perf_counter()
        results = engine.run_until_idle(now=0.0, tick_dt=0.01)
        makespan = time.perf_counter() - t0
        assert len(results) == n
        assert engine.compile_stats() == warm, 'recompiled mid-serve'
        return n / makespan, engine.metrics

    full_rps, full_m = serve()                              # PR6 baseline
    cached_rps, cached_m = serve(cache_interval=interval, exit_tol=exit_tol,
                                 exit_patience=patience)
    # quality pass: probe the scheduled outputs against the eager
    # full-step fp32 reference (probe excluded from the timed runs)
    _, qual_m = serve(n=3, quality_probe=1, cache_interval=interval,
                      exit_tol=exit_tol, exit_patience=patience)

    s = cached_m.summary()
    fq = qual_m.frontier()['fp32']
    f_full = full_m.frontier()['fp32']
    f_cached = cached_m.frontier()['fp32']
    emit('cache_serving/full_step_rps', round(full_rps, 3), 'req/s')
    emit('cache_serving/cached_rps', round(cached_rps, 3), 'req/s')
    emit('cache_serving/speedup', round(cached_rps / full_rps, 2), 'x')
    emit('cache_serving/cache_interval', interval, 'ticks')
    emit('cache_serving/cache_hit_rate', round(s['cache_hit_rate'], 3),
         'fraction')
    emit('cache_serving/early_exits', int(s['early_exits']), 'requests')
    emit('cache_serving/steps_saved', int(s['steps_saved']), 'steps')
    emit('cache_serving/mean_steps_executed',
         round(f_cached['mean_steps_executed'], 2), 'steps')
    emit('cache_serving/full_energy_per_req',
         round(f_full['mean_energy_j'] * 1e3, 4), 'mJ')
    emit('cache_serving/cached_energy_per_req',
         round(f_cached['mean_energy_j'] * 1e3, 4), 'mJ')
    emit('cache_serving/energy_reduction',
         round(f_full['mean_energy_j'] / f_cached['mean_energy_j'], 2),
         'x')
    emit('cache_serving/psnr_vs_full_fp32', round(fq['mean_psnr_db'], 2),
         'dB')
    emit('cache_serving/zero_recompiles', 1, 'bool')


# child of bench_coldstart: one full serve cold start in a FRESH process
# (pipeline init + warmup + first tick), persisting compilations to the
# cache dir in argv[1] and reporting the timings as JSON on stdout.
_COLDSTART_CHILD = r"""
import json, os, sys, time
os.environ['JAX_PLATFORMS'] = 'cpu'
t_proc = time.perf_counter()
import jax
from repro.diffusion.pipeline import DiffusionPipeline
from repro.models.unet import UNetConfig
from repro.serving import (ContinuousBatchingEngine, GenerationRequest,
                           cache_entries)
cfg = UNetConfig('bench-coldstart', img_size=16, in_ch=3, base_ch=32,
                 ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(8,),
                 n_heads=4, timesteps=50)
pipe = DiffusionPipeline.init(jax.random.PRNGKey(0), cfg)
engine = ContinuousBatchingEngine(pipe, slots=2, quality_probe=0)
warmup_s = engine.warmup(cache_dir=sys.argv[1])
engine.submit(GenerationRequest(request_id=0, seed=1, steps=2), now=0.0)
engine.run_until_idle(now=0.0)
print(json.dumps({'warmup_s': warmup_s,
                  'first_tick_s': engine.metrics.first_tick_s,
                  'proc_s': time.perf_counter() - t_proc,
                  'cache_entries': cache_entries(sys.argv[1])}))
"""


def _coldstart_child(cache_dir):
    env = dict(os.environ)
    env['PYTHONPATH'] = os.path.join(ROOT, 'src') + (
        os.pathsep + env['PYTHONPATH'] if env.get('PYTHONPATH') else '')
    env['JAX_PLATFORMS'] = 'cpu'
    out = subprocess.run([sys.executable, '-c', _COLDSTART_CHILD, cache_dir],
                         env=env, capture_output=True, text=True,
                         timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(f'coldstart child failed:\n{out.stderr[-2000:]}')
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_coldstart(emit):
    """Cold vs warm restart, measured across REAL process boundaries:
    the same serve bring-up (pipeline init, engine warmup, first tick)
    runs twice in fresh subprocesses sharing one persistent compilation
    cache directory.  Run 1 (cold, empty dir) pays the recompile storm
    and persists every executable; run 2 (warm) loads them from disk —
    the time-to-first-tick gap is what the persistent cache buys a
    restarted server."""
    with tempfile.TemporaryDirectory(prefix='repro-xla-cache-') as d:
        cold = _coldstart_child(d)
        assert cold['cache_entries'] > 0, 'cold run persisted nothing'
        warm = _coldstart_child(d)
    emit('coldstart/cold_warmup', round(cold['warmup_s'], 3), 's')
    emit('coldstart/warm_warmup', round(warm['warmup_s'], 3), 's')
    emit('coldstart/cold_first_tick', round(cold['first_tick_s'], 3), 's')
    emit('coldstart/warm_first_tick', round(warm['first_tick_s'], 3), 's')
    emit('coldstart/warmup_speedup',
         round(cold['warmup_s'] / max(warm['warmup_s'], 1e-9), 2), 'x')
    emit('coldstart/first_tick_speedup',
         round(cold['first_tick_s'] / max(warm['first_tick_s'], 1e-9), 2),
         'x')
    emit('coldstart/cache_entries', int(cold['cache_entries']), 'files')


# child of bench_sharded_serving: one process with 8 simulated host
# devices sweeps slot-sharded engines over 1/2/4/8-device meshes on a
# fixed request batch, counting scheduler ticks and wall time, and
# anchors the device-parallel model with the 1-device engine's measured
# tick time.  Reports JSON on stdout.
_SHARDED_CHILD = r"""
import json, os, sys, time
os.environ['JAX_PLATFORMS'] = 'cpu'
import jax
from repro.diffusion.pipeline import DiffusionPipeline
from repro.launch.mesh import serving_mesh
from repro.models.unet import UNetConfig
from repro.serving import ContinuousBatchingEngine, GenerationRequest

cfg = UNetConfig('bench-sharded', img_size=16, in_ch=3, base_ch=32,
                 ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(8,),
                 n_heads=4, timesteps=50)
pipe = DiffusionPipeline.init(jax.random.PRNGKey(0), cfg)
N, SPD, STEPS = 32, 2, 6

def run(n_dev, overlap=None, measure=False):
    e = ContinuousBatchingEngine(pipe, slots_per_device=SPD,
                                 mesh=serving_mesh(n_dev), quality_probe=0,
                                 overlap_decode=overlap)
    e.warmup()
    stats0 = e.compile_stats()
    for i in range(N):
        e.submit(GenerationRequest(request_id=i, seed=700 + i, steps=STEPS,
                                   exit_tol=0.0), now=0.0)
    out, ticks = [], 0
    t0 = time.perf_counter()
    while e.busy:
        out.extend(e.tick(now=0.0))
        ticks += 1
    wall = time.perf_counter() - t0
    assert len(out) == N, f'{n_dev}dev: {len(out)}/{N} completed'
    assert e.compile_stats() == stats0, f'{n_dev}dev recompiled mid-serve'
    r = {'slots': e.slots, 'ticks': ticks, 'wall_s': wall,
         'overlapped': e.metrics.overlapped_decodes}
    if measure:
        r['tick_s'] = e.measure_tick_s(steps=16)
    return r

report = {'n_devices': jax.device_count(), 'n_requests': N, 'runs': {}}
for n in (1, 2, 4, 8):
    report['runs'][str(n)] = run(n, measure=(n == 1))
report['overlap_on'] = run(8, overlap=True)
report['overlap_off'] = run(8, overlap=False)
print('REPORT ' + json.dumps(report))
"""


def _sharded_child():
    env = dict(os.environ)
    env['PYTHONPATH'] = os.path.join(ROOT, 'src') + (
        os.pathsep + env['PYTHONPATH'] if env.get('PYTHONPATH') else '')
    env['JAX_PLATFORMS'] = 'cpu'
    env['XLA_FLAGS'] = (env.get('XLA_FLAGS', '')
                        + ' --xla_force_host_platform_device_count=8').strip()
    out = subprocess.run([sys.executable, '-c', _SHARDED_CHILD],
                         env=env, capture_output=True, text=True,
                         timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f'sharded child failed:\n{out.stderr[-2000:]}')
    lines = [l for l in out.stdout.splitlines() if l.startswith('REPORT ')]
    if not lines:
        raise RuntimeError(f'sharded child printed no report:\n{out.stdout}')
    return json.loads(lines[-1][len('REPORT '):])


def bench_sharded_serving(emit):
    """Slot-sharded serving throughput at 1/2/4/8 devices, plus decode
    overlap on/off at 8 devices.

    The mesh is simulated on the host
    (``--xla_force_host_platform_device_count=8``), which SERIALIZES the
    per-device programs on one CPU — simulation wall clock cannot show
    device parallelism.  Slot sharding keeps the per-device program
    identical at every mesh size (same per-device batch, same kernels),
    so one tick of an N-device mesh takes one 1-device tick of wall
    time on real hardware; device-parallel throughput is therefore
    modeled as ``requests / (ticks * measured 1-device tick time)`` —
    the same measured-tick model the overload section uses for capacity.
    The serialized simulation wall rates are also emitted so the model
    is auditable against what actually ran."""
    rep = _sharded_child()
    assert rep['n_devices'] == 8, 'host device simulation failed'
    n_req = rep['n_requests']
    tick1 = rep['runs']['1']['tick_s']
    modeled = {}
    for n in (1, 2, 4, 8):
        r = rep['runs'][str(n)]
        modeled[n] = n_req / (r['ticks'] * tick1)
        emit(f'sharded_serving/rps_{n}dev', round(modeled[n], 2), 'req/s')
    speedup = modeled[8] / modeled[1]
    assert speedup > 1.5, f'8-device speedup {speedup:.2f}x <= 1.5x'
    emit('sharded_serving/speedup_8v1', round(speedup, 2), 'x')
    emit('sharded_serving/slots_8dev', rep['runs']['8']['slots'], 'slots')
    emit('sharded_serving/ticks_1dev', rep['runs']['1']['ticks'], 'ticks')
    emit('sharded_serving/ticks_8dev', rep['runs']['8']['ticks'], 'ticks')
    emit('sharded_serving/sim_wall_rps_1dev',
         round(n_req / rep['runs']['1']['wall_s'], 2), 'req/s')
    emit('sharded_serving/sim_wall_rps_8dev',
         round(n_req / rep['runs']['8']['wall_s'], 2), 'req/s')
    on, off = rep['overlap_on'], rep['overlap_off']
    assert on['overlapped'] > 0, 'decode overlap never engaged'
    emit('sharded_serving/overlap_on_rps',
         round(n_req / on['wall_s'], 2), 'req/s')
    emit('sharded_serving/overlap_off_rps',
         round(n_req / off['wall_s'], 2), 'req/s')
    emit('sharded_serving/overlapped_decodes', on['overlapped'], 'decodes')
    emit('sharded_serving/zero_recompiles', 1, 'bool')


def bench_overload(emit):
    """Survival under 5x overload: a Poisson trace offering five times
    the engine's measured service capacity hits a bounded deadline-aware
    queue.  The engine must complete what fits, shed the rest (tallied
    by cause), keep the queue at or under its bound, and never let a
    deadline-dead request occupy a slot."""
    import jax
    from repro.diffusion.pipeline import DiffusionPipeline
    from repro.models.unet import UNetConfig
    from repro.serving import (AdmissionQueue, ContinuousBatchingEngine,
                               GenerationRequest, overload_factor)
    cfg = UNetConfig('bench-overload', img_size=16, in_ch=3, base_ch=32,
                     ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(8,),
                     n_heads=4, timesteps=50)
    pipe = DiffusionPipeline.init(jax.random.PRNGKey(0), cfg)
    N, slots, steps, depth, factor = 40, 4, 6, 8, 5.0
    engine = ContinuousBatchingEngine(
        pipe, slots=slots, quality_probe=0,
        queue=AdmissionQueue(max_depth=depth, shed_policy='deadline-aware'))
    engine.warmup()
    tick_s = engine.measure_tick_s(steps=steps)
    capacity_rps = slots / (steps * tick_s)
    rate = factor * capacity_rps
    slo_ms = 3.0 * steps * tick_s * 1e3
    rng = np.random.default_rng(11)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, N))
    trace = [GenerationRequest(request_id=i, seed=500 + i, steps=steps,
                               arrival_time=float(arrivals[i]),
                               slo_ms=slo_ms) for i in range(N)]
    results = engine.replay(trace)
    s = engine.metrics.summary()
    by = engine.metrics.shed_by_reason
    assert len(results) + int(s['shed']) == N, 'requests lost'
    assert s['max_queue_depth'] <= depth, 'queue bound broken'
    assert s['shed'] > 0, '5x overload must shed'
    emit('overload/offered_x',
         round(overload_factor(rate, tick_s, steps, slots), 2), 'x')
    emit('overload/capacity', round(capacity_rps, 2), 'req/s')
    emit('overload/offered', round(rate, 2), 'req/s')
    emit('overload/completed', len(results), 'requests')
    emit('overload/shed', int(s['shed']), 'requests')
    emit('overload/shed_rate', round(s['shed'] / N, 3), 'fraction')
    emit('overload/shed_evicted', by.get('deadline_evict', 0), 'requests')
    emit('overload/shed_expired', by.get('expired', 0), 'requests')
    emit('overload/shed_queue_full', by.get('queue_full', 0), 'requests')
    emit('overload/max_queue_depth', int(s['max_queue_depth']), 'requests')
    emit('overload/queue_bound', depth, 'requests')
    emit('overload/p50_queue_wait', round(s['p50_queue_wait_ms'], 1), 'ms')
    emit('overload/p99_queue_wait', round(s['p99_queue_wait_ms'], 1), 'ms')
    emit('overload/slo', round(slo_ms, 1), 'ms')


def bench_obs_overhead(emit):
    """The observability tax: the SAME request batch served with tracing
    disabled (the zero-cost NULL_TRACER default) and enabled (a live
    ``Tracer`` recording the engine's submit/tick/admit/plan/dispatch/
    drain spans and the submit/slot-assign/request events).  Hot paths guard on ``tracer.enabled``, so the traced run
    must stay within 5% of the untraced requests/s — asserted on the
    best-of-3 makespans per mode so scheduler noise cannot fail the
    gate.  Also reports the event volume one run records."""
    import jax
    from repro.diffusion.pipeline import DiffusionPipeline
    from repro.models.unet import UNetConfig
    from repro.obs import Tracer
    from repro.serving import ContinuousBatchingEngine, GenerationRequest
    cfg = UNetConfig('bench-obs', img_size=16, in_ch=3, base_ch=32,
                     ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(8,),
                     n_heads=4, timesteps=50)
    pipe = DiffusionPipeline.init(jax.random.PRNGKey(0), cfg)
    N, slots, steps, reps = 10, 4, 6, 3
    engine = ContinuousBatchingEngine(pipe, slots=slots, quality_probe=0)
    engine.warmup()

    def serve(tracer):
        from repro.obs import NULL_TRACER
        saved = engine.tracer
        engine.tracer = tracer if tracer is not None else NULL_TRACER
        for i in range(N):
            engine.submit(GenerationRequest(
                request_id=i, seed=300 + i, steps=steps, exit_tol=0.0),
                now=0.0)
        t0 = time.perf_counter()
        results = engine.run_until_idle(now=0.0, tick_dt=0.01)
        makespan = time.perf_counter() - t0
        engine.tracer = saved
        assert len(results) == N
        return makespan

    # interleave modes so drift (thermal, background load) hits both
    plain_times, traced_times, tracers = [], [], []
    for _ in range(reps):
        plain_times.append(serve(None))
        tracers.append(Tracer())
        traced_times.append(serve(tracers[-1]))
    plain, traced = min(plain_times), min(traced_times)
    events = max(len(tr) for tr in tracers)
    plain_rps, traced_rps = N / plain, N / traced
    overhead = max(0.0, 1.0 - traced_rps / plain_rps)
    assert overhead < 0.05, \
        f'tracing overhead {overhead:.1%} >= 5% ' \
        f'({plain_rps:.2f} -> {traced_rps:.2f} req/s)'
    emit('obs_overhead/untraced_rps', round(plain_rps, 3), 'req/s')
    emit('obs_overhead/traced_rps', round(traced_rps, 3), 'req/s')
    emit('obs_overhead/overhead', round(overhead, 4), 'fraction')
    emit('obs_overhead/events_per_run', events, 'events')
    emit('obs_overhead/events_per_request', round(events / N, 1), 'events')


SECTIONS = {
    'table1': bench_table1,
    'fig8': bench_fig8,
    'fig9_fig10': bench_fig9_fig10,
    'deepcache': bench_deepcache,
    'dse': bench_dse,
    'kernels': bench_kernels,
    'serving': bench_serving,
    'quant_serving': bench_quant_serving,
    'cache_serving': bench_cache_serving,
    'coldstart': bench_coldstart,
    'overload': bench_overload,
    'sharded_serving': bench_sharded_serving,
    'obs_overhead': bench_obs_overhead,
}

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), '..')
BENCH_JSON = os.path.join(ROOT, 'BENCH_PR10.json')


def load_bench(path):
    """Read a BENCH_*.json into {name: value}, accepting both row shapes:
    the current ``{name, value, unit}`` and the pre-PR7
    ``{name, us_per_call, derived}`` (where the quantity of record lived
    in the ``derived`` string)."""
    with open(path) as f:
        doc = json.load(f)
    out = {}
    for row in doc.get('rows', []):
        if 'value' in row:
            out[row['name']] = row['value']
            continue
        val = row.get('derived', '')
        try:
            val = float(val)
        except (TypeError, ValueError):
            pass
        out[row['name']] = val
    return out


def _newest_prior_bench(include_current=False):
    """Newest BENCH_PR<k>.json at the repo root (highest k wins — the
    stacked-PR sequence is the clock).  Persist runs exclude the file
    this run writes (it may hold a half-written previous attempt); the
    ``--check`` gate includes it, because once committed it IS the
    newest agreed-on baseline."""
    best, best_k = None, -1
    for path in glob.glob(os.path.join(ROOT, 'BENCH_PR*.json')):
        if (not include_current
                and os.path.abspath(path) == os.path.abspath(BENCH_JSON)):
            continue
        m = re.search(r'BENCH_PR(\d+)\.json$', path)
        if m and int(m.group(1)) > best_k:
            best, best_k = path, int(m.group(1))
    return best


def check_regression(rows, guard='serving/engine_rps', tol=None,
                     fail=False):
    """Compare this run's ``guard`` metric against the newest committed
    BENCH_PR*.json.  Default mode warns on stderr and returns the
    message (or None); gate mode (``fail=True``, i.e. ``--check``) also
    errors when the guard metric or a baseline is missing — a gate that
    silently checks nothing is worse than no gate.  Returns
    (message_or_None, ok) in gate mode.  ``tol`` defaults to the
    ``BENCH_TOL`` env var (fraction, 0.10) so slower CI hardware can
    loosen the gate without editing code."""
    if tol is None:
        tol = float(os.environ.get('BENCH_TOL', '0.10'))
    new = {name: val for name, val, _ in rows}
    prior = _newest_prior_bench(include_current=fail)

    def _result(msg, ok):
        if msg:
            sys.stderr.write(msg + '\n')
        return (msg, ok) if fail else msg

    if guard not in new:
        if fail:
            return _result(f'[benchmarks] GATE ERROR: guard metric '
                           f'{guard!r} was not produced by this run — '
                           f'did you skip the serving section?', False)
        return _result(None, True)
    if prior is None:
        if fail:
            return _result('[benchmarks] GATE ERROR: no committed '
                           'BENCH_PR*.json baseline to compare against',
                           False)
        return _result(None, True)
    try:
        old = load_bench(prior).get(guard)
        old = float(old) if old is not None else None
        cur = float(new[guard])
    except (TypeError, ValueError):
        old = None
    if not old or old <= 0:
        if fail:
            return _result(f'[benchmarks] GATE ERROR: baseline '
                           f'{os.path.basename(prior)} has no usable '
                           f'{guard!r} value', False)
        return _result(None, True)
    if cur < (1.0 - tol) * old:
        kind = 'FAIL' if fail else 'WARNING'
        return _result(
            f'[benchmarks] {kind}: {guard} regressed '
            f'{(1 - cur / old) * 100:.1f}% vs {os.path.basename(prior)}'
            f' ({old:.3f} -> {cur:.3f} req/s, tolerance {tol:.0%})',
            False)
    if fail:
        return _result(
            f'[benchmarks] gate OK: {guard} {cur:.3f} req/s vs '
            f'{old:.3f} in {os.path.basename(prior)} '
            f'(tolerance {tol:.0%})', True)
    return _result(None, True)


def main() -> None:
    rows = []

    def emit(name, value, unit):
        rows.append((name, value, unit))
        print(f'{name},{value},{unit}', flush=True)

    argv = sys.argv[1:]
    check = '--check' in argv
    names = [a for a in argv if a != '--check'] or list(SECTIONS)
    unknown = [n for n in names if n not in SECTIONS]
    if unknown:
        sys.exit(f'unknown section(s) {unknown}; pick from {list(SECTIONS)}')
    print('name,value,unit')
    for n in names:
        SECTIONS[n](emit)
    if check:
        # merge gate: compare vs the committed baseline, never persist
        _, ok = check_regression(rows, fail=True)
        sys.exit(0 if ok else 1)
    check_regression(rows)
    # strict JSON on disk: a NaN/inf value (e.g. an unprobed PSNR mean)
    # becomes null instead of a bare NaN token no parser accepts
    from repro.obs.export import sanitize
    doc = sanitize({'sections': names,
                    'rows': [{'name': n, 'value': v, 'unit': u}
                             for n, v, u in rows]})
    with open(BENCH_JSON, 'w') as f:
        json.dump(doc, f, indent=2, allow_nan=False)
        f.write('\n')
    sys.stderr.write(f'[benchmarks] {len(rows)} rows -> {BENCH_JSON}\n')


if __name__ == '__main__':
    main()
