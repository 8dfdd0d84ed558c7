"""Observability tests: tracer semantics, zero-cost-when-disabled,
JSONL/Chrome exporters (strict JSON), Prometheus exposition, the
snapshot reporter, metrics summary symmetry (p99 + shed breakdown), the
engine end-to-end trace <-> metrics reconciliation, and the engine's
spans, program names and UNet scopes as a ``jax.profiler`` trace shows
them."""
import glob
import json
import os

import jax
import numpy as np
import pytest

from repro.diffusion.pipeline import DiffusionPipeline
from repro.distributed.fault_tolerance import StepMonitor
from repro.models.autoencoder import VAEConfig
from repro.models.unet import UNetConfig
from repro.obs import (NULL_TRACER, SnapshotReporter, Tracer, chrome_trace,
                       read_jsonl, render_exposition, sanitize,
                       write_chrome_trace, write_jsonl)
from repro.obs.export import QUEUE_TID, SCHEDULER_TID
from repro.serving import (AdmissionQueue, ContinuousBatchingEngine,
                           GenerationRequest, GenerationResult,
                           ServingMetrics)

pytestmark = pytest.mark.obs

TINY = UNetConfig('tiny-obs', img_size=16, in_ch=3, base_ch=32,
                  ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(8,),
                  n_heads=4, timesteps=16)


@pytest.fixture(scope='module')
def pipe():
    return DiffusionPipeline.init(jax.random.PRNGKey(0), TINY)


def _strict(text):
    """json.loads that rejects NaN/Infinity tokens."""
    def boom(tok):
        raise AssertionError(f'non-strict JSON token {tok!r}')
    return json.loads(text, parse_constant=boom)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_tracer_records_ordered_nested_spans():
    tr = Tracer()
    with tr.region('outer', cat='engine'):
        tr.instant('mark', cat='engine')
        with tr.region('inner', cat='engine'):
            pass
    names = [e.name for e in tr.events]
    # instants append immediately; spans append at region exit, so the
    # inner span lands before the outer one
    assert names == ['mark', 'inner', 'outer']
    inner, outer = tr.spans('inner')[0], tr.spans('outer')[0]
    assert outer.ts <= inner.ts
    assert outer.ts + outer.dur >= inner.ts + inner.dur
    assert all(e.ph == 'X' for e in tr.spans())
    assert len(tr) == 3


@pytest.mark.smoke
def test_tracer_explicit_timestamps_and_select():
    tr = Tracer()
    tr.instant('shed', cat='queue', ts=1.5, rid=7, reason='queue_full')
    tr.complete('request', 1.0, 3.0, cat='request', rid=7)
    tr.counter('occupancy', ts=2.0, active=3, queued=1)
    assert tr.select('shed')[0].ts == 1.5
    assert tr.spans('request')[0].dur == 2.0
    assert tr.select(ph='C')[0].args == {'active': 3, 'queued': 1}
    # negative-duration spans clamp to zero rather than corrupting a view
    assert tr.complete('bad', 5.0, 4.0).dur == 0.0


@pytest.mark.smoke
def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    assert Tracer().enabled is True
    before = len(NULL_TRACER)
    assert NULL_TRACER.instant('x') is None
    assert NULL_TRACER.complete('x', 0.0, 1.0) is None
    assert NULL_TRACER.counter('x', v=1) is None
    with NULL_TRACER.region('x'):
        pass
    assert len(NULL_TRACER) == before == 0


@pytest.mark.smoke
def test_trace_event_to_dict_drops_none_ids():
    tr = Tracer()
    e = tr.instant('submit', cat='queue', ts=0.5, rid=3)
    d = e.to_dict()
    assert d['rid'] == 3
    assert 'slot' not in d and 'device' not in d and 'tick' not in d
    assert 'dur' not in d                     # instants carry no duration
    s = tr.complete('step', 0.0, 0.25, cat='tick', tick=4).to_dict()
    assert s['dur'] == 0.25 and s['tick'] == 4


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_sanitize_rewrites_non_finite_floats():
    out = sanitize({'a': float('nan'), 'b': [1.0, float('inf')],
                    'c': {'d': -float('inf'), 'e': 'txt'}, 'f': 3})
    assert out == {'a': None, 'b': [1.0, None],
                   'c': {'d': None, 'e': 'txt'}, 'f': 3}


@pytest.mark.smoke
def test_jsonl_roundtrip(tmp_path):
    tr = Tracer()
    tr.instant('submit', cat='queue', ts=0.1, rid=0, psnr=float('nan'))
    tr.complete('request', 0.1, 0.9, cat='request', rid=0, slot=1)
    path = str(tmp_path / 'events.jsonl')
    assert write_jsonl(tr, path) == 2
    lines = open(path).read().splitlines()
    assert len(lines) == 2
    for line in lines:
        _strict(line)                          # every line is strict JSON
    back = read_jsonl(path)
    assert back[0]['name'] == 'submit'
    assert back[0]['args']['psnr'] is None     # NaN -> null
    assert back[1]['dur'] == pytest.approx(0.8)


@pytest.mark.smoke
def test_chrome_trace_lanes_and_strict_json(tmp_path):
    tr = Tracer()
    tr.instant('submit', cat='queue', ts=0.0, rid=0)
    tr.complete('request', 0.0, 1.0, cat='request', rid=0, slot=2,
                device=1, psnr=float('nan'))
    tr.complete('tick', 0.0, 0.5, cat='tick', tick=0)
    doc = chrome_trace(tr)
    rows = doc['traceEvents']
    by_name = {r['name']: r for r in rows if r['ph'] not in 'M'}
    # lane mapping: queue -> QUEUE_TID, slot-scoped -> 1+slot, else sched
    assert by_name['submit']['tid'] == QUEUE_TID
    assert by_name['request']['tid'] == 3
    assert by_name['tick']['tid'] == SCHEDULER_TID
    # seconds -> microseconds, instants scoped to their thread
    assert by_name['request']['dur'] == pytest.approx(1e6)
    assert by_name['submit']['s'] == 't'
    assert by_name['request']['args']['psnr'] is None
    assert by_name['request']['args']['rid'] == 0
    meta = {r['args']['name'] for r in rows if r['ph'] == 'M'}
    assert {'serving engine', 'scheduler', 'queue',
            'slot 2 (dev 1)'} <= meta
    path = str(tmp_path / 'trace.json')
    assert write_chrome_trace(tr, path) == len(rows)
    _strict(open(path).read())


# ---------------------------------------------------------------------------
# metrics symmetry + exposition
# ---------------------------------------------------------------------------

def _result(rid, submit=0.0, start=0.5, finish=1.0, **kw):
    return GenerationResult(request_id=rid, image=np.zeros((2, 2, 3)),
                            steps=4, submit_time=submit, start_time=start,
                            finish_time=finish, **kw)


@pytest.mark.smoke
def test_percentile_edge_cases():
    assert ServingMetrics._percentile([], 50) == 0.0
    assert ServingMetrics._percentile([2.5], 99) == 2.5


@pytest.mark.smoke
def test_summary_p99_and_shed_breakdown():
    m = ServingMetrics()
    for i in range(4):
        m.record_submit(0.0)
        m.record_complete(_result(i, finish=1.0 + i))
    m.record_shed('queue_full')
    m.record_shed('queue_full')
    m.record_shed('expired')
    s = m.summary()
    assert s['p99_latency_ms'] == pytest.approx(4000.0)
    assert s['p99_latency_ms'] >= s['p95_latency_ms'] >= s['p50_latency_ms']
    assert s['shed'] == 3.0
    assert s['shed_queue_full'] == 2.0
    assert s['shed_expired'] == 1.0
    snap = m.snapshot()
    assert snap.p99_latency_s >= snap.p95_latency_s


@pytest.mark.smoke
def test_render_exposition_format():
    m = ServingMetrics()
    m.record_submit(0.0)
    m.record_complete(_result(0), slo_ms=100.0)
    m.record_shed('queue_full')
    text = render_exposition(m, active_slots=2, queued=1)
    lines = text.splitlines()
    assert '# HELP repro_serving_completed_total Requests completed' in lines
    assert '# TYPE repro_serving_completed_total counter' in lines
    assert 'repro_serving_completed_total 1' in lines
    assert 'repro_serving_shed_total{reason="queue_full"} 1' in lines
    assert 'repro_serving_active_slots 2' in lines
    assert 'repro_serving_queued 1' in lines
    assert any(l.startswith('repro_serving_latency_seconds'
                            '{quantile="0.99"}') for l in lines)
    assert 'repro_serving_latency_seconds_count 1' in lines
    # summary _sum accumulates the raw latency, not a percentile
    assert 'repro_serving_latency_seconds_sum 1' in lines
    # every sample line's metric name was declared by a HELP/TYPE pair
    declared = {l.split(' ')[2] for l in lines if l.startswith('# TYPE')}
    for line in lines:
        if line.startswith('#'):
            continue
        name = line.split('{')[0].split(' ')[0]
        base = name[:-len('_sum')] if name.endswith('_sum') else (
            name[:-len('_count')] if name.endswith('_count') else name)
        assert base in declared, f'undeclared sample {name}'


@pytest.mark.smoke
def test_snapshot_reporter_interval_and_force():
    clock = [0.0]
    out = []
    rep = SnapshotReporter(interval_s=5.0, emit=out.append,
                           clock=lambda: clock[0])
    m = ServingMetrics()
    m.record_submit(0.0)
    m.record_complete(_result(0))
    # first call arms the interval without reporting
    assert rep.maybe_report(metrics=m) is None
    clock[0] = 3.0
    assert rep.maybe_report(metrics=m) is None
    clock[0] = 6.0
    line = rep.maybe_report(metrics=m, active_slots=1, queued=2)
    assert line is not None and 'completed=1/1' in line
    assert 'active=1' in line and 'queued=2' in line
    assert rep.maybe_report(metrics=m, force=True) is not None
    assert out == [line, line] or len(out) == 2
    assert rep.reports == 2
    with pytest.raises(ValueError):
        SnapshotReporter(interval_s=0.0)


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

def test_engine_trace_reconciles_with_metrics(pipe):
    """The acceptance invariant: a traced run's request spans agree with
    the metrics ledger — same completed count, identical per-request
    latency (spans are stamped from the result's own timing fields) —
    and every shed request has exactly one attributed shed instant."""
    tr = Tracer()
    engine = ContinuousBatchingEngine(
        pipe, slots=2, quality_probe=0, tracer=tr,
        queue=AdmissionQueue(max_depth=1))
    for i in range(6):
        engine.submit(GenerationRequest(request_id=i, seed=i, steps=3),
                      now=0.0)
    results = engine.run_until_idle(now=0.0, tick_dt=0.01)
    m = engine.metrics
    assert m.completed == len(results) > 0
    assert engine.queue.shed > 0

    spans = tr.spans('request')
    assert len(spans) == m.completed
    for s in spans:
        res = next(r for r in results if r.request_id == s.rid)
        assert s.dur == pytest.approx(res.latency_s, abs=1e-9)
        assert s.args['trace_id'] == f'req-{s.rid}'
        assert s.args['precision'] == 'fp32'
    sheds = tr.select('shed')
    assert len(sheds) == engine.queue.shed
    assert all(e.args['reason'] == 'queue_full' for e in sheds)
    # request-lifecycle instants pair off with the admitted population;
    # every submit call is an engine span, accepted or shed
    assert len(tr.select('submit', cat='queue')) == m.submitted
    assert len(tr.spans('submit', cat='engine')) == 6
    assert len(tr.select('slot_assign')) == m.completed
    # one drain span per completed request, carrying its id; the request
    # span keeps the energy the result reports
    drains = tr.spans('drain')
    assert sorted(s.rid for s in drains) == sorted(
        r.request_id for r in results)
    assert all(s.args['energy_j'] == next(
        r for r in results if r.request_id == s.rid).energy_j > 0
        for s in spans)
    # dispatch spans cover every tick's step calls
    dispatches = tr.spans('dispatch')
    assert dispatches and all(s.tick is not None for s in dispatches)
    assert sum(s.args['slots'] for s in dispatches) == m.unet_steps
    ticks = tr.spans('tick')
    assert len(ticks) == m.ticks
    occ = tr.select('occupancy', ph='C')
    assert len(occ) == m.ticks
    assert all(set(e.args) == {'active', 'queued'} for e in occ)


def test_engine_default_tracer_records_nothing(pipe):
    before = len(NULL_TRACER)
    engine = ContinuousBatchingEngine(pipe, slots=2, quality_probe=0)
    assert engine.tracer is NULL_TRACER
    engine.submit(GenerationRequest(request_id=0, seed=0, steps=2), now=0.0)
    engine.run_until_idle(now=0.0)
    assert len(NULL_TRACER) == before == 0


def test_engine_warmup_not_traced(pipe):
    """Warmup's throwaway requests must not pollute the trace: the only
    record is one engine-scoped warmup span."""
    tr = Tracer()
    engine = ContinuousBatchingEngine(pipe, slots=2, quality_probe=0,
                                      tracer=tr)
    engine.warmup()
    assert tr.spans('request') == []
    assert tr.select('submit') == []
    warm = tr.spans('warmup')
    assert len(warm) == 1
    assert warm[0].args['seconds'] > 0


def test_trace_id_threads_through(pipe):
    tr = Tracer()
    engine = ContinuousBatchingEngine(pipe, slots=1, quality_probe=0,
                                      tracer=tr)
    engine.submit(GenerationRequest(request_id=0, seed=0, steps=2,
                                    trace_id='gateway-abc'), now=0.0)
    res = engine.run_until_idle(now=0.0)[0]
    assert res.trace_id == 'gateway-abc'
    assert tr.spans('request')[0].args['trace_id'] == 'gateway-abc'
    assert tr.select('submit')[0].args['trace_id'] == 'gateway-abc'


def test_straggler_callback_edge_triggered(pipe):
    """on_straggler fires once per flagged-set CHANGE, with a matching
    trace instant — a persistent straggler does not refire every tick."""
    tr = Tracer()
    calls = []
    engine = ContinuousBatchingEngine(pipe, slots=2, quality_probe=0,
                                      tracer=tr,
                                      on_straggler=calls.append)
    engine.monitor = StepMonitor(n_hosts=4, window=4, min_samples=2)
    for _ in range(4):
        for host in (0, 1, 2):
            engine.monitor.record(host, 0.010)
        engine.monitor.record(3, 0.100)       # 10x the fleet median
    report = engine._poll_straggler()
    assert report is not None and report.slow_hosts == [3]
    assert [r.slow_hosts for r in calls] == [[3]]
    # same flagged set again: edge-triggered, no refire
    engine._poll_straggler()
    assert len(calls) == 1
    ev = tr.select('straggler')
    assert len(ev) == 1
    assert ev[0].args['slow_devices'] == [3]
    assert 're-mesh' in ev[0].args['recommendation']


def test_shed_attribution_per_request(pipe):
    """Expired requests are attributed by id in the trace (the queue's
    on_shed hook), not just counted."""
    tr = Tracer()
    engine = ContinuousBatchingEngine(pipe, slots=1, quality_probe=0,
                                      tracer=tr)
    engine.submit(GenerationRequest(request_id=0, seed=0, steps=2),
                  now=0.0)
    engine.submit(GenerationRequest(request_id=1, seed=1, steps=2,
                                    slo_ms=50.0), now=0.0)
    # tick far past request 1's deadline: it expires at admission
    results = engine.run_until_idle(now=10.0, tick_dt=0.01)
    assert [r.request_id for r in results] == [0]
    sheds = tr.select('shed')
    assert len(sheds) == 1
    assert sheds[0].rid == 1 and sheds[0].args['reason'] == 'expired'
    assert engine.metrics.shed_by_reason == {'expired': 1}


def test_user_on_shed_hook_chains(pipe):
    """A caller-installed queue on_shed still fires after the engine
    wires its own (trace + metrics) hook in."""
    seen = []
    q = AdmissionQueue(max_depth=1,
                       on_shed=lambda reason, req, now:
                       seen.append((reason, req.request_id)))
    engine = ContinuousBatchingEngine(pipe, slots=1, quality_probe=0,
                                      queue=q)
    for i in range(3):
        engine.submit(GenerationRequest(request_id=i, seed=i, steps=2),
                      now=0.0)
    engine.run_until_idle(now=0.0)
    assert seen == [('rejected', 2)] or seen == [('rejected', 1),
                                                 ('rejected', 2)]
    assert engine.metrics.shed_by_reason.get('queue_full') == len(seen)


# ---------------------------------------------------------------------------
# spans and program names in a jax.profiler trace
# ---------------------------------------------------------------------------

HOST = '/host:CPU'
ENGINE_SPANS = ('engine.tick', 'engine.admit', 'engine.plan',
                'engine.dispatch', 'engine.drain')


@pytest.fixture(scope='module')
def ldm_pipe():
    """A tiny latent pipeline: every engine program, the decode too."""
    vae = VAEConfig(img_size=16, in_ch=3, z_ch=4, base_ch=16,
                    ch_mults=(1, 2), groups=8)
    unet = UNetConfig('tiny-obs-ldm', img_size=8, in_ch=4, base_ch=32,
                      ch_mults=(1, 2), n_res_blocks=1, attn_resolutions=(4,),
                      n_heads=4, timesteps=16, latent=True)
    return DiffusionPipeline.init(jax.random.PRNGKey(0), unet, vae_cfg=vae)


def _profiled_events(log_dir):
    """(plane, name, start_ns, end_ns, stats) of every event of the
    trace written under ``log_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, '**', '*.xplane.pb'),
                            recursive=True))[-1]
    return [(plane.name, ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events]


@pytest.mark.parametrize('recording', [True, False],
                         ids=['tracer', 'null_tracer'])
def test_engine_spans_in_profiler_trace(ldm_pipe, tmp_path, recording):
    """A profiled run puts the engine's phases on the host plane, nested
    in their tick, and every program under its stable name — with or
    without a recording tracer; NULL_TRACER still records nothing."""
    tracer = Tracer() if recording else None
    engine = ContinuousBatchingEngine(ldm_pipe, slots=2, quality_probe=0,
                                      tracer=tracer)
    engine.warmup()
    before = len(NULL_TRACER)
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        engine.submit(GenerationRequest(request_id=i, seed=i, steps=2),
                      now=0.0)
    results = engine.run_until_idle(now=0.0)
    jax.profiler.stop_trace()
    evs = _profiled_events(str(tmp_path))
    host = {}
    for plane, name, a, b, stats in evs:
        if plane == HOST and name.startswith('engine.'):
            host.setdefault(name, []).append((a, b, stats))
    assert set(ENGINE_SPANS) <= set(host)
    ticks = host['engine.tick']
    assert len(ticks) == engine.metrics.ticks
    assert [t[2]['tick'] for t in ticks] == list(range(len(ticks)))
    for name in ('engine.drain', 'engine.dispatch'):
        for a, b, _ in host[name]:
            assert any(ta <= a and b <= tb for ta, tb, _ in ticks), name
    drains = host['engine.drain']
    assert sorted(d[2]['rid'] for d in drains) == sorted(
        r.request_id for r in results) == [0, 1, 2]
    assert len(host['engine.submit']) == 3
    modules = {stats.get('hlo_module') for *_, stats in evs}
    assert {'jit_vae_decode', 'jit_place_row', 'jit_take_row',
            'jit_init_noise'} <= modules
    assert 'jit_step_fp32' in modules
    assert not any(str(m).startswith('jit__lambda') for m in modules)
    if recording:
        assert len(tracer.spans('drain')) == 3
    else:
        assert len(NULL_TRACER) == before == 0


def test_program_names(pipe):
    """Every step variant runs as ``jit_step...``; no helper does; the
    same names key ``compile_stats``."""
    name = ContinuousBatchingEngine.step_label
    assert name('fp32', True) == 'step_fp32_guided'
    assert name('fp32', True, refresh=True) == 'step_fp32_refresh_guided'
    assert name('w8a8+noise', False, refresh=False) == \
        'step_w8a8_noise_skip'
    labels = {name(p, g, r) for p in ('fp32', 'w8a8', 'w8a8+noise')
              for g in (False, True) for r in (None, False, True)}
    assert len(labels) == 18 and all(n.startswith('step_') for n in labels)
    engine = ContinuousBatchingEngine(pipe, slots=2, quality_probe=0)
    engine.warmup()
    assert set(engine.compile_stats()) == {'step_fp32', 'init_noise',
                                           'place_row', 'take_row'}


def test_step_hlo_carries_block_scopes(pipe):
    """The compiled step's op metadata names the UNet block of each op."""
    engine = ContinuousBatchingEngine(pipe, slots=2, quality_probe=0)
    text = engine.aot_warmup()['compiled']['step_fp32'].as_text()
    assert 'HloModule jit_step_fp32,' in text
    for scope in ('t_embed', 'conv_in', 'resblock', 'attn', 'downsample',
                  'upsample', 'conv_out'):
        assert f'/{scope}/' in text, scope
    # blocks never nest: no op sits in two block scopes
    assert '/resblock/attn/' not in text and '/attn/resblock/' not in text
