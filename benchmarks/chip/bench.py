#!/usr/bin/env python3
"""Chip benchmark of the diffusion-serving engine: one cell per run.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run it from the root of a checkout on a machine with a TPU; everything
runs in this one process.  A cell is an entry of ``BENCHMARK.json``'s
``workloads``: a configuration (``configs/<name>.json``) under a traffic
mix (``traffic/<name>.json``).  The run:

1. draws the weights from the seed on the device in one compiled program
   (``weights.py``, in the layout of the configuration's model module,
   ``models.py``) and builds the engine the way ``serve --diffusion``
   does (``launch.serve.build_engine(pipe=..., slots=...)``), over the
   noise schedule the configuration states;
2. warms up with one throwaway request of the cell's own kind through
   ``submit`` / ``tick``, then runs the mix (``traffic.py``) through a
   ramp of about one turnover so the slots hold requests of mixed ages;
3. measures for ``--seconds``: a closed loop refills the queue at every
   completion, an open loop submits each request when it is due; every
   request is timed from its due time to its image on the host.  An open
   loop then runs on until the percentiles read over the window's
   requests are fixed (``settled``), or every one of them has finished;
4. reads ``peak_bytes_in_use``, frees the engine and compares a sample
   of the images served since the window opened, drawn from the seed
   with the longest request in it, with the plain reference of the
   configuration's model module run over the same requests;
   ``limits/<cell>.json`` holds the limit;
5. prints the metrics: with ``--trace 0`` the cell's end-to-end ones,
   with ``--trace 1`` (the window under the profiler) its per-layer ones,
   each read by ``metrics/<name>.py``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each compared number with its
limit); the last lines of standard error repeat the compared numbers.
It exits non-zero and prints no result when JAX finds no TPU, fewer
chips than the cell asks for, or ``REPRO_KERNELS`` forcing a mode other
than ``pallas``.  Compiles are kept in ``.jax_cache/`` at the checkout's
root, so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, 'metrics'))

import models  # noqa: E402
from _common import latencies, percentile, service_times  # noqa: E402

#: host annotations the benchmark wraps its own calls in (read by the
#: trace reduction to label idle gaps)
WINDOW = 'bench.window'

#: seconds an open loop waits after the window for the answers still due;
#: one that has not come by then never came
DRAIN_LIMIT_S = 150.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files named there
# ---------------------------------------------------------------------------

def cell_spec(name: str, root: str = ROOT):
    """Everything a run of cell ``name`` needs, read from the files."""
    bench = load_json(os.path.join(root, 'BENCHMARK.json'))
    cells = {w['name']: w for w in bench['workloads']}
    if name not in cells:
        raise SystemExit(f'unknown workload {name!r}: {sorted(cells)}')
    cell = cells[name]
    conf = {c['name']: c for c in bench['configs']}[cell['config']]
    cfg_path = os.path.join(root, conf['file'])
    cfg = load_json(cfg_path)
    if 'model' not in cfg:
        raise SystemExit(f'{cfg_path}: no "model": name the module of '
                         'this directory that holds its model')
    models.of(cfg)
    e2e = [m for m in bench['end_to_end']
           if name in m.get('workloads', [name])]
    reported = {m['name'] for m in e2e}
    per_layer = [m for m in bench['per_layer']
                 if name in m.get('workloads', [name])
                 and m['moves'] in reported]
    return {
        'name': name,
        'chips': int(cell['chips']),
        'config': cfg,
        'traffic': load_json(os.path.join(HERE, 'traffic',
                                          cell['traffic'] + '.json')),
        'limits': load_json(os.path.join(HERE, 'limits', name + '.json')),
        'end_to_end': e2e,
        'per_layer': per_layer,
    }


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(HERE, 'metrics', metric + '.py')
    spec = importlib.util.spec_from_file_location(
        'bench_metric_' + metric.replace('.', '_'), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# device and compile bookkeeping
# ---------------------------------------------------------------------------

def check_device(chips: int):
    """The devices, or None (with the reason on stderr) when this is not
    a TPU host with at least ``chips`` chips or the Pallas kernels are
    switched off."""
    forced = os.environ.get('REPRO_KERNELS')
    if forced and forced != 'pallas':
        log(f'bench: REPRO_KERNELS={forced} would bypass the Pallas kernels')
        return None
    import jax
    devs = jax.devices()
    if devs[0].platform != 'tpu':
        log(f'bench: JAX found no TPU (platform {devs[0].platform!r})')
        return None
    if len(devs) < chips:
        log(f'bench: the cell needs {chips} chips, JAX found {len(devs)}')
        return None
    return devs


class Compiles:
    """Counts compilations (persistent-cache misses) and executables
    obtained (compiled or loaded), with the time of each."""

    def __init__(self):
        import jax
        self.misses = 0
        self.hits = 0
        self.obtained = []          # (perf_counter, name, seconds)
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event == '/jax/compilation_cache/cache_misses':
            self.misses += 1
        elif event == '/jax/compilation_cache/cache_hits':
            self.hits += 1

    def _duration(self, event, duration, **kw):
        if event == '/jax/core/compile/backend_compile_duration':
            self.obtained.append((time.perf_counter(),
                                  kw.get('fun_name', '?'), duration))

    def since(self, t0):
        return [o for o in self.obtained if o[0] >= t0]


def enable_cache(cache_dir: str):
    import jax
    os.makedirs(cache_dir, exist_ok=True)
    os.environ['JAX_COMPILATION_CACHE_DIR'] = cache_dir
    jax.config.update('jax_compilation_cache_dir', cache_dir)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)


def derive(seed: int, n: int):
    """``n`` independent 31-bit seeds from the run's seed."""
    state = np.random.SeedSequence(seed % 2 ** 64).generate_state(n)
    return [int(s) for s in state & 0x7FFFFFFF]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def build_engine(cfg, params, ctx_seed: int):
    """The served engine over the benchmark's weights, with the noise
    schedule of the configuration's ``schedule``: the program's
    ``<kind>_schedule(timesteps, **the other keys)``."""
    import jax
    from repro.diffusion import schedule as schedules
    from repro.diffusion.pipeline import DiffusionPipeline
    from repro.launch import serve
    from repro.models.autoencoder import VAEConfig
    from repro.models.unet import UNetConfig

    def frozen(d, cls, **kw):
        names = set(cls.__dataclass_fields__)
        return cls(**{k: tuple(v) if isinstance(v, list) else v
                      for k, v in d.items() if k in names}, **kw)
    u, v = cfg['unet'], cfg.get('vae')
    sched = dict(cfg['schedule'])
    kind = sched.pop('kind')
    make = getattr(schedules, kind + '_schedule', None)
    if make is None:
        raise ValueError(f'repro.diffusion.schedule has no {kind}_schedule')
    pipe = DiffusionPipeline(
        frozen(u, UNetConfig, name=cfg['name']), params['unet'],
        make(u['timesteps'], **sched),
        None if v is None else frozen(v, VAEConfig), params['vae'])
    engine = serve.build_engine(pipe=pipe, slots=int(cfg['slots']),
                                seed=ctx_seed)
    jax.block_until_ready(engine.x)
    return engine


class Record:
    __slots__ = ('rid', 'steps', 'seed', 'due', 'start', 'finish', 'image')

    def __init__(self, rid, steps, seed, due):
        self.rid, self.steps, self.seed, self.due = rid, steps, seed, due
        self.start = self.finish = None
        self.image = None


class Loop:
    """Drives the engine with the cell's traffic and times every request
    from its due time."""

    def __init__(self, engine, mix, seed: int):
        import jax
        from repro.serving import GenerationRequest
        self.jax = jax
        self.Request = GenerationRequest
        self.engine = engine
        self.mix = mix
        from traffic import Stream
        self.stream = Stream(mix, seed)
        self.records = {}
        self.ticks = 0
        self.guidance = float(mix.get('guidance', 0.0))
        self.precision = mix.get('precision', 'fp32')
        self.keep_images = False
        self.window = None
        self.lost = []

    def _ann(self, name):
        return self.jax.profiler.TraceAnnotation(name)

    def submit(self, due: float):
        steps, seed = self.stream.next_request()
        rid = len(self.records)
        self.records[rid] = Record(rid, steps, seed, due)
        with self._ann('bench.submit'):
            ok = self.engine.submit(self.Request(
                request_id=rid, seed=seed, steps=steps,
                guidance=self.guidance, precision=self.precision),
                now=time.perf_counter())
        if not ok:
            raise RuntimeError(f'request {rid} was refused')

    def tick(self):
        with self._ann('bench.tick'):
            done = self.engine.tick(wall_clock=True)
        self.ticks += 1
        with self._ann('bench.results'):
            for res in done:
                rec = self.records[res.request_id]
                rec.start, rec.finish = res.start_time, res.finish_time
                if self.keep_images:
                    rec.image = res.image
        return done

    def note_starts(self):
        """Record the slot assignment time of each request still
        running."""
        for a in self.engine._slot:
            if a is not None and a.request.request_id in self.records:
                self.records[a.request.request_id].start = a.start_time

    def warm(self):
        """One throwaway request of the cell's kind: compiles (or loads)
        every program the traffic reaches."""
        eng = self.engine
        eng.submit(self.Request(request_id=-1, seed=0, steps=2,
                                guidance=self.guidance,
                                precision=self.precision), now=0.0)
        while eng.busy:
            eng.tick(wall_clock=True)


def run_closed(loop: Loop, seconds: float, on_open):
    mix = loop.mix
    now = time.perf_counter()
    for _ in range(int(mix['outstanding'])):
        loop.submit(now)
    done = 0
    while done < int(mix['ramp_completions']):
        for _ in loop.tick():
            loop.submit(time.perf_counter())
            done += 1
    on_open()
    t_open = time.perf_counter()
    loop.window = (t_open, t_open + seconds)
    loop.keep_images = True
    ticks0 = loop.ticks
    with loop._ann(WINDOW):
        while time.perf_counter() < loop.window[1]:
            for _ in loop.tick():
                loop.submit(time.perf_counter())
    loop.window_ticks = loop.ticks - ticks0


def settled(loop: Loop, recs, now: float, q) -> bool:
    """Whether the run may stop: every request of ``recs`` has finished,
    or (with a quantile ``q``) the ``q``-th and every lower percentile of
    their latencies and service times are fixed.  They are once each
    request still running has been due, and in its slot, for longer than
    that percentile as it stands with the unfinished counted as infinite:
    its own value, whatever it turns out to be, then ranks above it."""
    running = [r for r in recs if r.finish is None]
    if not running:
        return True
    if q is None:
        return False
    loop.note_starts()
    if any(r.start is None for r in running):
        return False
    p_lat = percentile(latencies(recs), q)
    p_srv = percentile(service_times(recs), q)
    return all(now - r.due >= p_lat and now - r.start >= p_srv
               for r in running)


def run_open(loop: Loop, seconds: float, on_open):
    mix = loop.mix
    t0 = time.perf_counter()
    t_open = t0 + float(mix['ramp_s'])
    t_close = t_open + seconds
    due = t0 + loop.stream.next_gap()
    drain_q = mix.get('drain_quantile')
    opened = False
    ticks0 = 0
    in_window = set()
    ann = None
    while True:
        now = time.perf_counter()
        if not opened and now >= t_open:
            on_open()
            opened = True
            t_open = time.perf_counter()
            t_close = t_open + seconds
            loop.window = (t_open, t_close)
            loop.keep_images = True
            ticks0 = loop.ticks
            ann = loop._ann(WINDOW)
            ann.__enter__()
        while due <= now and due < t_close:
            if opened and due >= t_open:
                in_window.add(len(loop.records))
            loop.submit(due)
            due += loop.stream.next_gap()
        if opened and ann is not None and now >= t_close:
            ann.__exit__(None, None, None)
            ann = None
            loop.window_ticks = loop.ticks - ticks0
        if opened and now >= t_close and settled(
                loop, [loop.records[r] for r in in_window], now, drain_q):
            break
        if opened and now >= t_close + DRAIN_LIMIT_S:
            loop.lost = [r for r in in_window
                         if loop.records[r].finish is None]
            break
        if loop.engine.busy:
            loop.tick()
        else:
            with loop._ann('bench.sleep'):
                wake = min(due, t_close) if now < t_close else now
                time.sleep(max(0.0, wake - time.perf_counter()))
    loop.in_window = in_window


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def pick_sample(records, n: int, seed: int):
    """The longest request and ``n - 1`` others, drawn from the seed."""
    recs = sorted(records, key=lambda r: r.rid)
    if not recs:
        return []
    rng = np.random.default_rng(seed)
    longest = max(r.steps for r in recs)
    tops = [r for r in recs if r.steps == longest]
    first = tops[int(rng.integers(len(tops)))]
    rest = [r for r in recs if r is not first]
    others = [rest[i] for i in sorted(rng.permutation(len(rest))[:n - 1])]
    return [first] + others


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare(cfg, params, sample, guidance, ctx_seed, controls=()):
    """Per request, the relative L2 distance between the served image and
    the reference image of the same request (the configuration's model
    module's ``sample``); and for each operand rounding named in
    ``controls``, the control's distances (its images computed from the
    same weights with that rounding)."""
    model = models.of(cfg)
    u = cfg['unet']
    ctx = None
    if u.get('context_dim') is not None:
        ctx = model.context_rows(ctx_seed, int(cfg['context_tokens']),
                                 u['context_dim'])

    def images(operands):
        return {rec.rid: model.sample(
            params['unet'], params['vae'], cfg, rec.seed, rec.steps,
            guidance, ctx, operands=operands) for rec in sample}
    ref = images(None)
    out = {rec.rid: rel_l2(rec.image, ref[rec.rid]) for rec in sample}
    ctrl = {}
    for name in controls:
        ctrl[name] = {rid: rel_l2(img, ref[rid])
                      for rid, img in images(name).items()}
    return out, ctrl


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def device_info(devs, peak):
    return {'platform': devs[0].platform, 'kind': devs[0].device_kind,
            'count': len(devs), 'memory_peak_bytes': peak}


def memory_peak(devs):
    peaks = [int((d.memory_stats() or {}).get('peak_bytes_in_use', 0))
             for d in devs]
    return max(peaks)


def run(spec, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, cache_dir: str = None,
        controls=()):
    """One run of a cell; returns the result dict (or None when the
    device check fails).  ``controls`` names operand roundings of the
    reference (the model module's ``OPERANDS``) to run over the sample as
    well, each a control reading."""
    if require_chip:
        devs = check_device(spec['chips'])
        if devs is None:
            return None
    enable_cache(cache_dir or os.path.join(ROOT, '.jax_cache'))
    if os.path.join(ROOT, 'src') not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, 'src'))
    import jax
    import weights
    if not require_chip:
        devs = jax.devices()[:spec['chips']]
    compiles = Compiles()
    cfg, mix = spec['config'], spec['traffic']
    w_seed, ctx_seed, t_seed, s_seed = derive(seed, 4)

    params = weights.make(cfg, w_seed)
    jax.block_until_ready(params)
    engine = build_engine(cfg, params, ctx_seed)
    loop = Loop(engine, mix, t_seed)
    loop.warm()
    t_warm = time.perf_counter()
    log(f'[setup] weights + engine + warm-up: {t_warm - T_PROCESS:.1f}s; '
        f'compiled {compiles.misses}, loaded {compiles.hits} from the cache')

    state = {}
    trace_dir = tempfile.mkdtemp(prefix='bench_trace_') if trace else None

    def on_open():
        state['setup_s'] = time.perf_counter() - T_PROCESS
        state['queued_open'] = len(engine.queue)
        state['misses_setup'] = compiles.misses
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        state['t_open'] = time.perf_counter()

    if mix['loop'] == 'closed':
        run_closed(loop, seconds, on_open)
    else:
        run_open(loop, seconds, on_open)
    state['queued_close'] = len(engine.queue)
    jax.block_until_ready(engine.x)
    if trace:
        jax.profiler.stop_trace()
    t_open, t_close = loop.window
    in_window_compiles = compiles.since(state['t_open'])
    peak = memory_peak(devs)

    recs = list(loop.records.values())
    finished = [r for r in recs if r.finish is not None
                and t_open <= r.finish <= t_close]
    due_in = [loop.records[r] for r in sorted(getattr(loop, 'in_window', []))]
    log(f'[window] {seconds:g}s: {len(finished)} images, '
        f'{loop.window_ticks} ticks, {len(in_window_compiles)} compiles '
        f'in the window; queued {state["queued_open"]} at the open, '
        f'{state["queued_close"]} at the close; '
        f'{sum(r.finish is None for r in due_in)} of {len(due_in)} due in '
        f'the window still running at the stop; peak_bytes_in_use {peak}')
    red = None
    if trace:
        import trace_reduce
        evs = trace_reduce.events(trace_reduce.find_xplane(trace_dir))
        span = trace_reduce.window(evs, WINDOW)
        red = trace_reduce.reduce(evs, *span)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # correctness: free the engine, then the reference over a sample
    served = [r for r in recs if r.image is not None]
    sample = pick_sample(served, int(spec['limits']['requests']), s_seed)
    bad = [r.rid for r in served if not np.isfinite(r.image).all()]
    bad += loop.lost
    del engine, loop.engine
    gc.collect()
    t_ref = time.perf_counter()
    gaps, ctrl = compare(cfg, params, sample, float(mix.get('guidance', 0)),
                         ctx_seed, controls)
    log(f'[reference] {len(sample)} requests in '
        f'{time.perf_counter() - t_ref:.1f}s')
    for rec in sample:
        log(f'[reference] request {rec.rid} ({rec.steps} steps): rel L2 '
            f'{gaps[rec.rid]:.4e}' + ''.join(
                f'; control {name} {c[rec.rid]:.4e}'
                for name, c in ctrl.items()))
    limit = float(spec['limits']['image_rel_l2'])
    worst = max(gaps.values()) if gaps else math.inf
    correct = bool(sample) and not bad and worst <= limit

    ctx = {
        'spec': spec, 'config': cfg, 'model': models.of(cfg),
        'traffic': mix, 'seconds': seconds,
        'window': (t_open, t_close), 'records': recs, 'finished': finished,
        'due_in_window': due_in, 'ticks': loop.window_ticks,
        'setup_s': state['setup_s'], 'peak_bytes': peak, 'trace': red,
        'device_kind': devs[0].device_kind, 'peaks': load_json(
            os.path.join(HERE, 'peaks.json')),
        'guided': float(mix.get('guidance', 0)) > 0
        and cfg['unet'].get('context_dim') is not None,
    }
    names = spec['per_layer'] if trace else spec['end_to_end']
    metrics = {}
    for m in names:
        value = reader(m['name'])(ctx)
        if value is not None:
            metrics[m['name']] = {'value': value, 'unit': m['unit']}
    device = device_info(devs, peak)
    out = {'correct': correct, 'attempted': len(due_in or finished),
           'failed': len(bad),
           'metrics': metrics, 'device': device}
    if trace:
        device['busy_s'] = red['busy_ns'] / 1e9
        device['window_s'] = red['window_ns'] / 1e9
        out['breakdown'] = {'device_ops': red['top_ops'],
                            'idle_gaps': red['idle_gaps']}
    if ctrl:
        out['control'] = {name: {'image_rel_l2_min': min(c.values()),
                                 'image_rel_l2_max': max(c.values())}
                          for name, c in ctrl.items()}
    out['compared'] = {'image_rel_l2_max': {'value': worst, 'limit': limit},
                       'answers_failed': {'value': len(bad), 'limit': 0}}
    extra = {'compiles_setup': state['misses_setup'],
             'compiles_in_window': len(in_window_compiles),
             'setup_s': state['setup_s']}
    log(f'[compiles] {json.dumps(extra)}')
    if trace:
        log(f'[trace] per module: {json.dumps(red["per_module_ns"])}')
        log(f'[trace] idle by label: {json.dumps(red["idle_by_label"])}')
    log(f'compared answers_failed {len(bad)} limit 0 '
        '(non-finite, or never came)')
    log(f'compared image_rel_l2_max {worst!r} limit {limit!r}')
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, 'src', 'repro')):
        log(f'bench: no system under test at {ROOT}/src/repro')
        return 2
    spec = cell_spec(args.workload)
    out = run(spec, args.seed, args.seconds, bool(args.trace))
    if out is None:
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
