"""Serving loop: batched LM decode (prefill + N decode steps) or
continuous-batching diffusion generation, with per-request precision
policies (paper C1: the W8A8 photonic path).

CPU-scale demos:
    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --preset smoke --tokens 16
    PYTHONPATH=src python -m repro.launch.serve --diffusion \
        --requests 8 --rate 4 --slots 4 --steps 6 --precision w8a8

A paper UNet at its published widths, with its VAE, seeded weights and a
seeded text-conditioning stand-in (every other request guided at 7.5);
this one needs an accelerator (``chip_smoke.py`` runs it on one TPU):
    PYTHONPATH=src python -m repro.launch.serve --diffusion \
        --model sd_v1_4 --requests 6 --slots 4 --steps 10

The diffusion mode replays a Poisson arrival trace through the
continuous-batching engine (``repro.serving``): requests arrive with
exponential inter-arrival times at ``--rate`` req/s, are multiplexed
into mixed-timestep UNet steps, and report p50/p95 latency, requests/s
and the per-request energy.  ``--precision`` selects each request's
execution policy — ``fp32`` (GPU digital baseline energy), ``w8a8``
(the analog MR-bank path, ~94x lower EPB) or ``w8a8+noise`` (8-bit plus
the analog perturbation model); quantized runs also print the PSNR/MSE
quality probe against the fp32 reference (the accuracy-vs-EPB frontier).

Cold-start and overload hardening:

Every XLA compilation goes through JAX's persistent on-disk cache, so a
restarted server *loads* its step variants instead of recompiling them —
the warmup line reports the wall seconds and whether the cache was warm.
The cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else
in ``--cache-dir PATH``, else in ``.jax_cache`` at the checkout's root.
``--overload X`` sizes the arrival rate at X times the engine's
*measured* service capacity
(``engine.measure_tick_s``), bounds the admission queue
(``--queue-depth``, default 2x slots) and turns on deadline-aware
shedding, then proves survival: the queue stays bounded, excess load is
shed (by cause), no deadline-dead request occupies a slot, and the
p50/p99 queue waits are reported:

    PYTHONPATH=src python -m repro.launch.serve --diffusion \
        --overload 5 --requests 32 --slots 4 --steps 6

Sharded multi-device serving: ``--devices N`` builds a 1-D ``('data',)``
mesh over the first N visible devices and shards the engine's slot axis
across it (``--slots-per-device`` fixes the per-device budget; decode
overlap is on by default, ``--overlap-decode off`` disables it).
``--resize-to M --resize-after K`` triggers an elastic resize to M
devices after K completions, mid-replay — the drop-and-survive demo.
``--cache-max-mb`` bounds the persistent compilation cache with LRU
eviction.  Simulate a mesh on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --diffusion \
        --devices 8 --slots-per-device 1 --requests 16 --rate 8 \
        --steps 6 --resize-to 4 --resize-after 4

Observability (``repro.obs``): ``--trace PATH`` records every request's
lifecycle (submit -> slot assign -> per-tick steps -> early exit ->
decode -> complete, plus sheds, warmup, resizes, stragglers) and writes
a Chrome/Perfetto ``trace_event`` timeline; ``--log-json PATH`` writes
the same events as a grep-able JSONL structured log; ``--prom PATH``
dumps the Prometheus text exposition of the final counters; and
``--report-every S`` prints an in-run metrics snapshot line every S
seconds.  After a traced replay the trace is reconciled against
``ServingMetrics`` (same completed/shed counts, identical latencies)
before it is written.  ``--log-level`` tunes verbosity; log lines keep
their ``[serve]`` / ``[mesh]`` / ``[overload]`` prefixes as logger
names:

    PYTHONPATH=src python -m repro.launch.serve --diffusion \
        --requests 8 --rate 4 --slots 4 --steps 6 \
        --trace /tmp/serve-trace.json --log-json /tmp/serve-events.jsonl
"""
from __future__ import annotations

import argparse
import logging
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.diffusion import CONTEXT_TOKENS
from repro.configs.registry import get, smoke_config
from repro.distributed import sharding as SH
from repro.launch import steps as ST
from repro.launch.mesh import make_mesh
from repro.models.layers import count_params

log_serve = logging.getLogger('serve')
log_mesh = logging.getLogger('mesh')
log_coldstart = logging.getLogger('coldstart')
log_overload = logging.getLogger('overload')
log_elastic = logging.getLogger('elastic')
log_sched = logging.getLogger('sched')
log_energy = logging.getLogger('energy')
log_frontier = logging.getLogger('frontier')
log_obs = logging.getLogger('obs')


def setup_logging(level: str = 'info', stream=None) -> None:
    """Leveled stdout logging with the historical ``[tag]`` prefixes:
    each subsystem logs through its own logger (``serve``, ``mesh``,
    ``overload``, ...) and the formatter renders the logger name as the
    line prefix, so ``--log-level debug`` tunes verbosity without
    changing the grep-able output shape."""
    logging.basicConfig(
        level=getattr(logging, level.upper()),
        format='[%(name)s] %(message)s',
        stream=stream if stream is not None else sys.stdout,
        force=True)


def serve_lm(cfg, mesh, batch: int, prompt_len: int, new_tokens: int,
             quant: bool = False, dtype=jnp.float32):
    params = ST.init_params(jax.random.PRNGKey(0), cfg)
    max_len = prompt_len + new_tokens
    state = ST.init_serve_state(cfg, batch, max_len, cache_dtype=dtype)
    prefill = jax.jit(ST.build_prefill_step(cfg, dtype=dtype, quant=quant))
    decode = jax.jit(ST.build_decode_step(cfg, dtype=dtype, quant=quant),
                     donate_argnums=(1,))
    rng = np.random.default_rng(0)
    batch_in = {'tokens': jnp.asarray(
        rng.integers(0, cfg.vocab, (batch, prompt_len)), jnp.int32)}
    if cfg.family == 'encdec':
        batch_in['frames'] = jnp.asarray(
            rng.normal(size=(batch, prompt_len, cfg.d_model)), dtype)
    with mesh:
        t0 = time.perf_counter()
        tok, state = prefill(params, state, batch_in)
        jax.block_until_ready(tok)
        t_prefill = time.perf_counter() - t0
        out = [tok]
        t0 = time.perf_counter()
        for i in range(new_tokens - 1):
            tok, state = decode(params, state, tok,
                                jnp.int32(prompt_len + i))
            out.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.perf_counter() - t0
    seqs = jnp.concatenate(out, axis=1)
    tps = batch * (new_tokens - 1) / max(t_decode, 1e-9)
    log_serve.info('prefill %d toks x%d: %.3fs; decode %d steps: %.3fs '
                   '(%.1f tok/s)', prompt_len, batch, t_prefill,
                   new_tokens - 1, t_decode, tps)
    return seqs


def poisson_trace(n: int, rate_hz: float, steps: int, seed: int = 0,
                  slo_ms=None, precision='fp32', guidance: float = 0.0):
    """Poisson arrival trace: n requests, exponential inter-arrivals.
    ``precision`` is one name, or a sequence cycled over request pairs;
    ``guidance`` is the classifier-free guidance scale of every other
    (odd-numbered) request.  With two precisions, four requests cover
    every (precision, guided) pair."""
    from repro.serving import GenerationRequest
    precisions = (precision,) if isinstance(precision, str) \
        else tuple(precision)
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, n))
    return [GenerationRequest(request_id=i, seed=1000 + i, steps=steps,
                              arrival_time=float(a), slo_ms=slo_ms,
                              precision=precisions[(i // 2) %
                                                   len(precisions)],
                              guidance=guidance if i % 2 else 0.0)
            for i, a in enumerate(arrivals)]


#: the default UNet: a small model for CPU demos and tests
TOY_MODEL = 'toy'

#: classifier-free guidance scale of the guided requests of a
#: text-conditioned model
GUIDANCE = 7.5


def build_pipeline(model: str = TOY_MODEL, img: int = 16, seed: int = 0):
    """The diffusion pipeline ``serve --diffusion`` serves, with weights
    drawn from ``seed`` (nothing is downloaded).  ``model`` is
    ``'toy'`` (a small ``img``-pixel UNet) or a name in
    ``configs.diffusion.PAPER_MODELS``, served at its published widths
    with its VAE.  A paper model that does not trace is refused here,
    before any weight is drawn."""
    from repro.configs.diffusion import PAPER_MODELS, PAPER_VAES
    from repro.diffusion.pipeline import DiffusionPipeline
    from repro.models.unet import UNetConfig, init_unet, unet_apply
    key = jax.random.PRNGKey(seed)
    if model == TOY_MODEL:
        cfg = UNetConfig('serve-diffusion', img_size=img, in_ch=3,
                         base_ch=64, ch_mults=(1, 2), n_res_blocks=1,
                         attn_resolutions=(img // 2,), n_heads=4,
                         timesteps=100)
        return DiffusionPipeline.init(key, cfg)
    if model not in PAPER_MODELS:
        raise ValueError(f'unknown model {model!r}: expected {TOY_MODEL!r} '
                         f'or one of {sorted(PAPER_MODELS)}')
    cfg = PAPER_MODELS[model]
    S = jax.ShapeDtypeStruct
    try:
        params = jax.eval_shape(lambda k: init_unet(k, cfg), key)
        ctx = None if cfg.context_dim is None else \
            S((1, CONTEXT_TOKENS, cfg.context_dim), jnp.float32)
        jax.eval_shape(
            lambda p, x, t, c: unet_apply(p, cfg, x, t, c), params,
            S((1, cfg.img_size, cfg.img_size, cfg.in_ch), jnp.float32),
            S((1,), jnp.int32), ctx)
    except Exception as e:
        raise ValueError(f'model {model!r} cannot be served: its UNet does '
                         f'not trace ({type(e).__name__}: {e})') from e
    return DiffusionPipeline.init(key, cfg, PAPER_VAES.get(model))


def build_context(pipe, slots: int, seed: int = 0):
    """The engine-wide conditioning of a text-conditioned UNet: one seeded
    ``(CONTEXT_TOKENS, context_dim)`` embedding standing in for a text
    encoder's output, repeated over the ``slots`` rows.  None for an
    unconditioned UNet."""
    dim = pipe.unet_cfg.context_dim
    if dim is None:
        return None
    row = jax.random.normal(jax.random.PRNGKey(seed + 1),
                            (1, CONTEXT_TOKENS, dim), jnp.float32)
    return jnp.broadcast_to(row, (slots, CONTEXT_TOKENS, dim))


def build_engine(model: str = TOY_MODEL, img: int = 16, slots: int = 4,
                 seed: int = 0, devices=None, slots_per_device=None,
                 pipe=None, **engine_kw):
    """Pipeline, conditioning, mesh and continuous-batching engine, as
    ``serve --diffusion`` sets them up.  ``devices`` shards the slot
    axis over a 1-D mesh of the first N visible devices; ``pipe`` reuses
    an already built pipeline (a second engine over the same weights);
    ``engine_kw`` pass through to ``ContinuousBatchingEngine``."""
    from repro.serving import ContinuousBatchingEngine
    from repro.serving.batcher import align_slots
    if pipe is None:
        pipe = build_pipeline(model, img, seed)
    mesh = None
    if devices is not None:
        from repro.launch.mesh import serving_mesh
        mesh = serving_mesh(n_devices=devices)
        slots = slots_per_device * devices if slots_per_device \
            else align_slots(slots, devices)
    return ContinuousBatchingEngine(
        pipe, slots=slots, context=build_context(pipe, slots, seed),
        mesh=mesh, slots_per_device=slots_per_device, **engine_kw)


def serve_diffusion(img: int, steps: int, n_requests: int, rate_hz: float,
                    slots: int, precision: str = 'fp32', seed: int = 0,
                    model: str = TOY_MODEL,
                    slo_ms=None, quality_probe: int = 1,
                    cache_interval: int = 1, exit_tol=None,
                    exit_patience: int = 2, cache_dir=None,
                    queue_depth=None, shed_policy: str = 'reject-newest',
                    overload: float = 0.0, devices=None,
                    slots_per_device=None, overlap_decode=None,
                    resize_to=None, resize_after=None, cache_max_mb=None,
                    trace_path=None, log_json_path=None, prom_path=None,
                    report_every=None):
    """Replay a Poisson arrival trace through the continuous-batching
    engine and print the serving + energy report, plus the per-policy
    accuracy-vs-EPB frontier.  ``model`` picks the UNet
    (``build_pipeline``); a text-conditioned one guides every other
    request at ``GUIDANCE``.  ``cache_interval > 1`` enables
    DeepCache-phased slotting (full UNet pass every ``cache_interval``
    ticks, shallow passes in between); ``exit_tol`` enables speculative
    early-exit draining once a request's x0 prediction stops moving.

    ``cache_dir`` wires the persistent compilation cache into warmup
    (cold run populates it; a restarted process loads from it).
    ``overload > 0`` ignores ``rate_hz`` and offers ``overload`` times
    the engine's measured service capacity, with a bounded queue
    (``queue_depth``, default ``2 * slots``) and deadline-aware
    shedding proving the engine survives instead of growing its backlog
    without bound.

    ``devices`` shards the slot axis over a 1-D mesh of the first N
    visible devices; ``resize_to``/``resize_after`` demo the elastic
    path by resizing the mesh mid-replay after K completions.

    ``trace_path`` / ``log_json_path`` enable per-request tracing and
    write the Chrome-trace timeline / JSONL structured log after the
    replay (reconciled against the metrics first); ``prom_path`` dumps
    the final Prometheus text exposition; ``report_every`` emits an
    in-run snapshot line every that-many seconds."""
    from repro.obs import (SnapshotReporter, Tracer, render_exposition,
                           write_chrome_trace, write_jsonl)
    from repro.serving import (AdmissionQueue, cache_entries,
                               enable_persistent_cache, overload_factor)

    queue = None
    if overload > 0:
        queue_depth = 2 * slots if queue_depth is None else queue_depth
        shed_policy = 'deadline-aware'
    if queue_depth is not None or shed_policy != 'reject-newest':
        queue = AdmissionQueue(max_depth=queue_depth,
                               shed_policy=shed_policy)
    tracer = Tracer() if (trace_path or log_json_path) else None
    reporter = None
    if report_every is not None and report_every > 0:
        reporter = SnapshotReporter(interval_s=report_every,
                                    emit=log_obs.info)

    def _on_straggler(report):
        log_mesh.warning('straggler flagged: hosts %s (median %.1fms, '
                         'threshold %.1fms) — %s', list(report.slow_hosts),
                         report.median_s * 1e3, report.threshold_s * 1e3,
                         report.recommendation)

    engine = build_engine(model, img, slots, devices=devices,
                          slots_per_device=slots_per_device, queue=queue,
                          quality_probe=quality_probe,
                          cache_interval=cache_interval, exit_tol=exit_tol,
                          exit_patience=exit_patience,
                          overlap_decode=overlap_decode, tracer=tracer,
                          reporter=reporter,
                          on_straggler=_on_straggler
                          if devices is not None else None)
    mesh = engine.mesh
    guidance = GUIDANCE if engine.context is not None else 0.0
    log_serve.info('model %s: %.2fM UNet parameters%s', model,
                   count_params(engine.pipe.unet_params) / 1e6,
                   ', VAE decode' if engine.pipe.vae_params is not None
                   else '')
    if mesh is not None:
        log_mesh.info('slot axis sharded over %d devices: %d slots '
                      '(%d/device), overlap_decode=%s', devices,
                      engine.slots, engine.slots // devices,
                      engine.overlap_decode)
    if cache_dir and cache_max_mb is not None:
        # enable with the size bound BEFORE warmup re-enables it (the
        # bound is process state the engine's trim_cache calls enforce)
        enable_persistent_cache(cache_dir,
                                max_bytes=int(cache_max_mb * 2 ** 20))
    entries_before = cache_entries(cache_dir) if cache_dir else 0
    log_serve.info('warmup (compile, policy=%s%s)...', precision,
                   f', cache_dir={cache_dir}' if cache_dir else '')
    warmup_s = engine.warmup(precisions=(precision,), cache_dir=cache_dir)
    if cache_dir:
        entries = cache_entries(cache_dir)
        state = 'warm (loaded from cache)' if entries_before > 0 \
            else f'cold (persisted {entries} executables)'
        log_coldstart.info('warmup %.2fs — %s', warmup_s, state)
    else:
        log_coldstart.info('warmup %.2fs (no persistent cache)', warmup_s)
    if overload > 0:
        tick_s = engine.measure_tick_s(steps=steps)
        capacity_rps = slots / (steps * tick_s)
        rate_hz = overload * capacity_rps
        if slo_ms is None:
            # default SLO: 3x the zero-queue service time — generous for
            # an uncontended request, certain to shed under overload
            slo_ms = 3.0 * steps * tick_s * 1e3
        log_overload.info(
            'measured capacity %.2f req/s (%.1f ms/tick) -> offering '
            '%.2f req/s = %.1fx, queue_depth=%s, slo=%.0fms, '
            'shed_policy=%s', capacity_rps, tick_s * 1e3, rate_hz,
            overload_factor(rate_hz, tick_s, steps, slots), queue_depth,
            slo_ms, shed_policy)
    trace = poisson_trace(n_requests, rate_hz, steps, seed, slo_ms=slo_ms,
                          precision=precision, guidance=guidance)
    sched = []
    if cache_interval > 1:
        sched.append(f'cache_interval={cache_interval}')
    if exit_tol is not None and exit_tol > 0:
        sched.append(f'exit_tol={exit_tol:g} patience={exit_patience}')
    log_serve.info('replaying %d requests at %.1f req/s (%d slots, %d '
                   'DDIM steps, precision=%s%s)', n_requests, rate_hz,
                   engine.slots, steps, precision,
                   ', ' + ', '.join(sched) if sched else '')
    resize_state = {'done': 0, 'fired': False, 'flushed': []}

    def _on_result(res):
        resize_state['done'] += 1
        k = resize_after if resize_after is not None else n_requests // 2
        if (resize_to is not None and not resize_state['fired']
                and resize_state['done'] >= k):
            resize_state['fired'] = True
            log_elastic.info('%d done -> resizing %s -> %d devices '
                             'mid-replay', resize_state['done'], devices,
                             resize_to)
            resize_state['flushed'].extend(engine.elastic_resize(
                n_devices=resize_to, precisions=(precision,)))
            log_elastic.info('rebuilt: %d slots on %d devices, %d parked',
                             engine.slots, resize_to, len(engine._parked))

    t0 = time.perf_counter()
    results = engine.replay(
        trace, on_result=_on_result if resize_to is not None else None)
    results.extend(resize_state['flushed'])
    makespan = time.perf_counter() - t0
    if engine.monitor is not None:
        report = engine.monitor.check()
        log_mesh.info('stragglers: %s',
                      report.recommendation if report else 'none detected')
    s = engine.metrics.summary()
    log_serve.info('%d done in %.2fs (%.2f req/s) p50=%.0fms p95=%.0fms '
                   'p99=%.0fms slo_viol=%d shed=%d', len(results),
                   makespan, s['requests_per_s'], s['p50_latency_ms'],
                   s['p95_latency_ms'], s['p99_latency_ms'],
                   int(s['slo_violations']), int(s['shed']))
    if overload > 0 or s['shed'] > 0:
        m = engine.metrics
        by = dict(m.shed_by_reason)
        log_overload.info(
            'survived: queue peaked at %d%s, shed %d/%d (queue_full=%d '
            'evicted=%d expired=%d), queue wait p50=%.0fms p99=%.0fms',
            int(s['max_queue_depth']),
            f'/{queue_depth}' if queue_depth is not None else '',
            int(s['shed']), n_requests, by.get('queue_full', 0),
            by.get('deadline_evict', 0), by.get('expired', 0),
            s['p50_queue_wait_ms'], s['p99_queue_wait_ms'])
        assert len(results) + int(s['shed']) == n_requests, \
            'requests lost: completed + shed != offered'
        if queue_depth is not None:
            assert s['max_queue_depth'] <= queue_depth, 'queue bound broken'
    if cache_interval > 1 or s['steps_saved'] > 0:
        log_sched.info('cache_hit_rate=%.2f early_exits=%d steps_saved=%d',
                       s['cache_hit_rate'], int(s['early_exits']),
                       int(s['steps_saved']))
    src = 'simulated DiffLight' if precision != 'fp32' \
        else 'GPU digital baseline'
    log_energy.info('%.2f mJ/request (%.1f mJ total, %s)',
                    s['energy_per_request_mj'], s['total_energy_mj'], src)
    for name, pt in engine.metrics.frontier().items():
        quality = '' if pt['probed'] == 0 else (
            f'  psnr={pt["mean_psnr_db"]:.1f}dB mse={pt["mean_mse"]:.2e}'
            f' (vs fp32 reference, {int(pt["probed"])} probed)')
        sched_cols = ''
        if pt['cache_hit_rate'] > 0 or pt['early_exits'] > 0:
            sched_cols = (f'  hit_rate={pt["cache_hit_rate"]:.2f}'
                          f' steps={pt["mean_steps_executed"]:.1f}'
                          f'/{pt["mean_steps_requested"]:.1f}')
        log_frontier.info('%s: %.3f pJ/bit  %.2f mJ/request%s%s', name,
                          pt['mean_epb_pj'], pt['mean_energy_j'] * 1e3,
                          sched_cols, quality)
    if tracer is not None:
        _reconcile_trace(tracer, engine)
        if trace_path:
            n = write_chrome_trace(tracer, trace_path)
            log_obs.info('chrome trace: %d events -> %s (open in '
                         'chrome://tracing or ui.perfetto.dev)', n,
                         trace_path)
        if log_json_path:
            n = write_jsonl(tracer, log_json_path)
            log_obs.info('structured event log: %d lines -> %s', n,
                         log_json_path)
    if prom_path:
        with open(prom_path, 'w') as f:
            f.write(render_exposition(engine.metrics))
        log_obs.info('prometheus exposition -> %s', prom_path)
    return results


def _reconcile_trace(tracer, engine) -> None:
    """Assert the trace agrees with the metrics ledger before export:
    one request span per completed request (with the span duration equal
    to the result latency by construction — spans are stamped from the
    result's own timing fields), one shed instant per shed request."""
    m = engine.metrics
    spans = tracer.spans('request')
    assert len(spans) == m.completed, \
        f'trace/metrics drift: {len(spans)} request spans vs ' \
        f'{m.completed} completed'
    sheds = tracer.select('shed')
    total_shed = sum(m.shed_by_reason.values())
    assert len(sheds) == total_shed, \
        f'trace/metrics drift: {len(sheds)} shed events vs ' \
        f'{total_shed} shed requests'
    log_obs.info('trace reconciled: %d request spans == %d completed, '
                 '%d shed events == %d shed (%d events total)',
                 len(spans), m.completed, len(sheds), total_shed,
                 len(tracer))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default='internlm2-1.8b')
    ap.add_argument('--preset', default='smoke', choices=['smoke', 'full'])
    ap.add_argument('--batch', type=int, default=2)
    ap.add_argument('--prompt', type=int, default=16)
    ap.add_argument('--tokens', type=int, default=16)
    ap.add_argument('--w8a8', action='store_true',
                    help='LM mode: quantized matmuls; diffusion mode: '
                         'deprecated alias for --precision w8a8')
    ap.add_argument('--precision', default=None,
                    choices=['fp32', 'w8a8', 'w8a8+noise'],
                    help='diffusion request precision policy '
                         '(default fp32; overrides --w8a8)')
    ap.add_argument('--quality-probe', type=int, default=1,
                    help='probe every k-th quantized request against the '
                         'fp32 reference (0 = off)')
    ap.add_argument('--diffusion', action='store_true',
                    help='serve diffusion requests (continuous batching)')
    ap.add_argument('--model', default=TOY_MODEL,
                    help=f'diffusion UNet: {TOY_MODEL!r} (a small --img-pixel '
                         'demo model) or a paper model of '
                         'configs/diffusion.py served at its published '
                         'widths with its VAE, e.g. sd_v1_4')
    ap.add_argument('--requests', type=int, default=8)
    ap.add_argument('--rate', type=float, default=4.0,
                    help='Poisson arrival rate, req/s')
    ap.add_argument('--slots', type=int, default=4)
    ap.add_argument('--steps', type=int, default=6,
                    help='DDIM steps per request (diffusion mode)')
    ap.add_argument('--img', type=int, default=16)
    ap.add_argument('--slo-ms', type=float, default=None)
    ap.add_argument('--cache-interval', type=int, default=1,
                    help='DeepCache refresh cadence: full UNet pass every '
                         'k ticks, shallow cached passes in between '
                         '(1 = caching off)')
    ap.add_argument('--exit-tol', type=float, default=None,
                    help='speculative early exit: drain a request once its '
                         'x0 prediction moves less than this relative '
                         'tolerance (None/0 = off)')
    ap.add_argument('--exit-patience', type=int, default=2,
                    help='consecutive converged ticks before early exit')
    ap.add_argument('--cache-dir', default=None,
                    help='persistent XLA compilation cache directory (a '
                         'restarted server loads its compiled step '
                         'variants from here instead of recompiling); '
                         '$JAX_COMPILATION_CACHE_DIR wins when set, and '
                         'the default is .jax_cache in the checkout')
    ap.add_argument('--queue-depth', type=int, default=None,
                    help='bound the admission queue (default: unbounded; '
                         '--overload defaults this to 2x slots)')
    ap.add_argument('--shed-policy', default='reject-newest',
                    choices=['reject-newest', 'deadline-aware'],
                    help='what to shed at the queue bound: the newest '
                         'arrival, or the entry with the least SLO slack')
    ap.add_argument('--overload', type=float, default=0.0,
                    help='offer this multiple of the measured service '
                         'capacity (ignores --rate; bounds the queue and '
                         'enables deadline-aware shedding). 5 = the '
                         'survival trace')
    ap.add_argument('--devices', type=int, default=None,
                    help='shard the slot axis over a 1-D mesh of the '
                         'first N visible devices (simulate with '
                         'XLA_FLAGS=--xla_force_host_platform_device_'
                         'count=N)')
    ap.add_argument('--slots-per-device', type=int, default=None,
                    help='per-device slot budget on the mesh (overrides '
                         '--slots; the invariant elastic resizes keep)')
    ap.add_argument('--overlap-decode', default='auto',
                    choices=['auto', 'on', 'off'],
                    help='pipeline drained requests\' VAE decodes behind '
                         'the next denoise tick (auto: on when sharded)')
    ap.add_argument('--resize-to', type=int, default=None,
                    help='elastic-resize the mesh to this many devices '
                         'mid-replay (the drop/rejoin survival demo)')
    ap.add_argument('--resize-after', type=int, default=None,
                    help='completions before the mid-replay resize '
                         '(default: half the requests)')
    ap.add_argument('--cache-max-mb', type=float, default=None,
                    help='bound the persistent compilation cache; '
                         'least-recently-used executables are evicted')
    ap.add_argument('--log-level', default='info',
                    choices=['debug', 'info', 'warning', 'error'],
                    help='stdout logging verbosity')
    ap.add_argument('--trace', default=None, metavar='PATH',
                    help='record per-request tracing and write a Chrome/'
                         'Perfetto trace_event timeline here (diffusion '
                         'mode)')
    ap.add_argument('--log-json', default=None, metavar='PATH',
                    help='write the structured JSONL event log here '
                         '(diffusion mode; same events as --trace)')
    ap.add_argument('--prom', default=None, metavar='PATH',
                    help='write the final Prometheus text exposition of '
                         'the serving metrics here (diffusion mode)')
    ap.add_argument('--report-every', type=float, default=None,
                    metavar='SECONDS',
                    help='print an in-run metrics snapshot line every '
                         'this many seconds (diffusion mode)')
    args = ap.parse_args()
    setup_logging(args.log_level)
    if args.diffusion:
        precision = args.precision or ('w8a8' if args.w8a8 else 'fp32')
        from repro.serving.compile_cache import default_cache_dir
        serve_diffusion(args.img, args.steps, args.requests, args.rate,
                        args.slots, precision=precision, model=args.model,
                        slo_ms=args.slo_ms,
                        quality_probe=args.quality_probe,
                        cache_interval=args.cache_interval,
                        exit_tol=args.exit_tol,
                        exit_patience=args.exit_patience,
                        cache_dir=default_cache_dir(args.cache_dir),
                        queue_depth=args.queue_depth,
                        shed_policy=args.shed_policy,
                        overload=args.overload,
                        devices=args.devices,
                        slots_per_device=args.slots_per_device,
                        overlap_decode=None if args.overlap_decode == 'auto'
                        else args.overlap_decode == 'on',
                        resize_to=args.resize_to,
                        resize_after=args.resize_after,
                        cache_max_mb=args.cache_max_mb,
                        trace_path=args.trace,
                        log_json_path=args.log_json,
                        prom_path=args.prom,
                        report_every=args.report_every)
        return
    cfg = smoke_config(args.arch) if args.preset == 'smoke' \
        else get(args.arch)
    mesh = make_mesh((1, 1), ('data', 'model'))
    seqs = serve_lm(cfg, mesh, args.batch, args.prompt, args.tokens,
                    quant=args.w8a8)
    log_serve.info('sample token ids: %s', np.asarray(seqs[0, :12]))


if __name__ == '__main__':
    main()
