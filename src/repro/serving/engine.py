"""Continuous-batching diffusion engine: slot-based mixed-timestep steps
with per-request precision policies.

The engine owns a fixed ``(slots, H, W, C)`` latent buffer.  Each slot
carries one in-flight request at its *own* DDIM step index — possible
because every denoise step is a single UNet call with a per-sample
timestep vector (``DiffusionPipeline.denoise_step``), so requests at
different denoising depths share one jitted step.  Each request also
carries its own *precision* (``fp32`` / ``w8a8`` / ``w8a8+noise``); the
engine resolves it to a frozen ``PrecisionPolicy`` and keeps one jitted
step per (policy, guided) pair.  Per tick:

  1. free slots are refilled from the admission queue (each new request's
     initial noise is derived from its own seed, exactly as
     ``samplers.ddim_sample`` would);
  2. active slots are grouped by precision (``batcher.group_by_precision``)
     and ONE fixed-shape mixed-timestep UNet step per group advances that
     group's slots (other slots are masked out, their latents unchanged) —
     so a mixed-precision tick costs one pre-compiled call per distinct
     policy, never a recompile;
  3. slots that reached the end of their trajectory drain through the
     (fixed batch-1) VAE decode, report metrics + policy-aware energy
     (w8a8 rides the DiffLight simulation; fp32 is billed the GPU digital
     baseline), and are immediately refillable.  Sampled quantized
     requests additionally run an eager fp32 reference for the same
     seed/steps/guidance and report PSNR/MSE against it — the per-request
     points of the accuracy-vs-EPB frontier.

Two cooperating schedulers make the per-tick step cost *dynamic*:

  * **DeepCache-phased slots** (``cache_interval > 1``): the engine owns
    a batched slot-axis feature-cache buffer ``(slots, ...)`` and keeps
    exactly TWO pre-compiled step variants per (policy, guided) pair —
    a *refresh* step (full UNet pass, rewrites the cache rows) and a
    *skip* step (shallow pass splicing in the cached deep features).
    All cache-enabled slots share one refresh cadence: admission snaps
    new requests onto phase 0 of the cadence (a queued request is held
    until the next refresh tick), so every skip tick is a whole-batch
    shallow pass.  The photonic accountant bills skip ticks through the
    DeepCache workload transform (``shallow_workload_fraction``) instead
    of a full-UNet tick.
  * **Speculative early-exit draining** (``exit_tol``): every step also
    surfaces the x0 prediction from ``samplers.ddim_step``; the engine
    tracks the per-slot relative change ``||x0_t - x0_{t-1}||`` and
    drains a slot whose prediction stayed within ``exit_tol`` for
    ``exit_patience`` consecutive ticks, committing the converged x0 as
    the result — per-request step counts become dynamic and the freed
    slot is immediately available to queued work.

Every device function is jitted once against fixed shapes — after one
warmup per policy (``warmup(precisions=...)``) the engine performs ZERO
recompilations, which ``compile_stats()`` exposes for tests to assert;
enabling caching adds exactly the refresh/skip pair per (policy,
guided), never more.

Cold-start and overload hardening:

  * ``warmup(..., cache_dir=...)`` routes every compilation through
    JAX's persistent on-disk cache (``serving/compile_cache.py``), so a
    restarted engine *loads* its step variants instead of recompiling —
    the recompile storm becomes a cache read.  ``aot_warmup`` pre-lowers
    and compiles every ``(precision, guided, refresh)`` step variant the
    request mix can reach (plus the fixed-shape helpers) WITHOUT running
    a tick, populating the persistent cache ahead of time.  Warmup wall
    time and the time-to-first-served-tick are recorded in the metrics
    (``warmup_s`` / ``first_tick_s``).
  * Overload: give the engine a bounded ``AdmissionQueue(max_depth=...,
    shed_policy='deadline-aware')`` and excess arrivals are shed instead
    of growing the backlog; at admission the engine expires queued
    requests whose deadline already passed, so a dead request never
    occupies a slot.  Sheds are tallied by cause in the metrics, along
    with p50/p99 queue wait and the peak queue depth.

Sharded multi-device serving (``mesh=...``): the slot axis shards over
the mesh's ``data`` axis, so ONE engine spans an N-device mesh with each
device carrying ``slots / N`` slot rows of the latent / x0 / DeepCache
buffers.  Every step variant runs once per device over its own rows
(``shard_map``, which the Pallas kernels need: they cannot be
partitioned automatically), with sharded ``out_shardings`` so donated
buffers stay resident and partitioned across ticks; ``_place`` /
``_take`` move single samples in and out of the sharded buffers without
ever materializing the whole buffer on one device.  The slot axis is
pure data parallelism — the UNet treats batch rows independently — so a
request served on the mesh matches the single-device engine: to float32
rounding on the CPU, and on a TPU up to where the two programs round
their bf16 matmul operands (``w8a8+noise`` excepted: each device draws the analog
noise for its own rows, deterministically).  Three things ride on top:

  * **Decode overlap** (``overlap_decode``, default on when sharded):
    draining a finished slot *dispatches* the VAE decode asynchronously
    and frees the slot immediately; the image materializes only after
    the NEXT denoise tick has been launched, so decode runs behind the
    following step instead of serializing with it.  Results surface one
    tick later (a final flush covers the last tick); the metrics count
    ``overlapped_decodes``.
  * **Elastic resize** (``elastic_resize``): when devices drop or
    rejoin, ``distributed.fault_tolerance.elastic_serving_plan`` sizes
    the new 1-D mesh and the engine rebuilds its slot buffer on it at a
    constant per-device slot budget, re-placing in-flight latents and
    *parking* any overflow on the host (parked requests re-enter slots
    as they free, ahead of the queue, with a forced cache refresh).
    Step variants are re-lowered for the new topology — ``aot_warmup``
    pre-compiles them without serving a tick — and a ``StepMonitor``
    (``engine.monitor``) keeps per-device tick timings so a deployment
    can trigger the resize from straggler reports.
  * **AOT warmup / persistent cache** carry through: the pre-lowered
    shapes are tagged with the mesh sharding, so the executables a
    sharded engine persists are the ones it serves with.

Output equivalence: with eta=0 DDIM is deterministic given the initial
noise, and both the UNet and the per-row w8a8 activation scales treat
batch elements independently, so a request served through the engine —
at fp32 OR w8a8 — is numerically identical to running
``DiffusionPipeline.generate(key=PRNGKey(seed), batch=1, steps=s,
policy=...)`` on its own (tests pin this at atol 1e-5).  ``w8a8+noise``
is deterministic under the engine's noise seed: two engines with the same
seed and request sequence produce identical images.

Observability (``repro.obs``).  The engine's phases are spans
(``Tracer.region``), named as a ``jax.profiler`` trace shows them:
``engine.submit``; ``engine.tick`` around the whole tick, and inside it
``engine.admit`` (expiry, unpark, pops, each new request's noise and slot
writes), ``engine.plan`` (the per-slot arrays and their copies to the
device), one ``engine.dispatch`` per step call (tagged tick, precision,
guided, refresh, slots), ``engine.exit_sync`` (the early-exit read, when
it runs) and one ``engine.drain`` per drained image (its take, decode
dispatch, host copy and bookkeeping; under decode overlap, the flush that
materializes it).  Every span is a profiler annotation whether or not
the engine records, so any ``jax.profiler`` trace of a serving process
puts each host phase on the device ops' clock.  Every device program has
a stable name: ``jit_step_<precision>[_refresh|_skip][_guided]``,
``jit_init_noise``, ``jit_place_row``, ``jit_take_row`` and
``jit_vae_decode``.  Construct with ``tracer=Tracer()`` and the engine
also records the spans on the serving clock, with every request's
lifecycle — submit, shed (with the specific victim, via the queue's
``on_shed`` hook), slot assignment, early exit, and a submit-to-finish
request span stamped from the SAME timing fields the metrics use (so
trace and metrics reconcile exactly) — plus engine-global events
(warmup, AOT lowering, elastic resize, straggler flags) and a per-tick
occupancy counter.  The default is the no-op ``NULL_TRACER``; every
hot-path event guards on ``tracer.enabled``, so an untraced engine
builds no event objects and its spans are annotations alone.
``on_straggler=`` registers a callback the ``StepMonitor`` fires when
its flagged-device set changes — the hook a deployment uses to trigger
``elastic_resize`` from measured straggle instead of a fixed schedule.
``engine.reporter`` (a ``SnapshotReporter``) emits periodic in-run
metric lines, checked once per tick.
"""
from __future__ import annotations

import dataclasses
import math
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as PSpec

from repro.core.precision import PrecisionPolicy
from repro.diffusion import samplers
from repro.diffusion.deepcache import unet_apply_cached
from repro.diffusion.pipeline import DiffusionPipeline, eps_fn
from repro.distributed.fault_tolerance import (StepMonitor,
                                               elastic_serving_plan)
from repro.distributed.sharding import named
from repro.models import autoencoder as AE
from repro.obs.tracer import NULL_TRACER, Tracer
from repro.serving.api import GenerationRequest, GenerationResult
from repro.serving.batcher import align_slots, plan_tick
from repro.serving.compile_cache import trim_cache
from repro.serving.metrics import PhotonicAccountant, ServingMetrics
from repro.serving.queue import AdmissionQueue, Queued


@dataclasses.dataclass
class _Active:
    """One occupied slot: the request plus its trajectory cursor and the
    scheduler state (resolved cache/early-exit knobs, eval counters)."""
    request: GenerationRequest
    ts: np.ndarray               # this request's DDIM timestep trajectory
    i: int                       # next step index into `ts`
    submit_time: float
    start_time: float
    cache_on: bool = False       # participates in the shared refresh cadence
    exit_tol: float = 0.0        # <= 0: early exit disabled
    exit_patience: int = 2
    full_evals: int = 0          # full-UNet ticks consumed so far
    cached_evals: int = 0        # shallow (skip) ticks consumed so far
    exit_streak: int = 0         # consecutive ticks under exit_tol
    force_refresh: bool = False  # next tick must be a full pass (set when
    #                              a parked slot re-enters: its DeepCache
    #                              feature rows did not survive the resize)


@dataclasses.dataclass
class _Pending:
    """A drained slot whose VAE decode has been dispatched but not
    materialized: under decode overlap the image syncs only after the
    NEXT tick's UNet step is in flight (``_finish_drain``)."""
    active: _Active
    z: 'jax.Array'               # decoded (or raw-latent) batch-1 array
    now: float
    wall_clock: bool
    early: bool
    slot: int = -1               # slot the request drained from (tracing)


class ContinuousBatchingEngine:
    def __init__(self, pipe: DiffusionPipeline, slots: int = 4,
                 context=None, queue: Optional[AdmissionQueue] = None,
                 metrics: Optional[ServingMetrics] = None,
                 photonic: Optional[PhotonicAccountant] = None,
                 track_energy: bool = True,
                 noise_model=None, noise_seed: int = 0,
                 quality_probe: int = 1,
                 cache_interval: int = 1,
                 exit_tol: Optional[float] = None,
                 exit_patience: int = 2,
                 exit_min_steps: int = 2,
                 mesh: Optional[Mesh] = None,
                 slots_per_device: Optional[int] = None,
                 overlap_decode: Optional[bool] = None,
                 tracer: Optional[Tracer] = None,
                 on_straggler=None,
                 reporter=None):
        """``noise_model`` / ``noise_seed`` configure the ``w8a8+noise``
        policy (defaults: the paper's analog perturbation model, seed 0).
        ``quality_probe``: run the full-step fp32 reference + PSNR/MSE
        probe for every k-th completed quantized / cached / early-exited
        request (0 disables probing).

        ``cache_interval``: the shared DeepCache refresh cadence — a full
        UNet pass every ``cache_interval`` ticks, shallow passes in
        between (1 = caching off).  ``exit_tol`` / ``exit_patience``:
        engine-wide speculative early-exit defaults (requests override
        per field; ``exit_tol=None`` leaves early exit off).
        ``exit_min_steps``: never early-exit before this many executed
        steps (at least 2 — the convergence signal needs two x0
        predictions).

        ``mesh``: a 1-D ``('data',)`` mesh (``launch.mesh.serving_mesh``)
        shards the slot axis of every buffer across its devices.
        ``slots_per_device`` overrides ``slots`` with a per-device budget
        (the invariant ``elastic_resize`` preserves); otherwise ``slots``
        is rounded up to divide the mesh.  ``overlap_decode`` (default:
        on exactly when sharded) pipelines drained requests' VAE decodes
        behind the next denoise tick.

        ``tracer``: a ``repro.obs.Tracer`` recording the lifecycle /
        engine event stream (default: the zero-cost ``NULL_TRACER``).
        ``on_straggler``: callback fired with a ``StragglerReport``
        whenever the ``StepMonitor``'s flagged-device set changes.
        ``reporter``: a ``repro.obs.SnapshotReporter`` polled once per
        tick for periodic in-run metric lines."""
        if slots < 1:
            raise ValueError('need at least one slot')
        if cache_interval < 1:
            raise ValueError('cache_interval must be >= 1')
        self._created = time.perf_counter()   # time-to-first-tick origin
        self.pipe = pipe
        self.mesh = mesh
        if mesh is not None:
            if 'data' not in mesh.axis_names:
                raise ValueError("serving mesh needs a 'data' axis")
            ndev = int(mesh.shape['data'])
            if slots_per_device is not None:
                if slots_per_device < 1:
                    raise ValueError('slots_per_device must be >= 1')
                slots = slots_per_device * ndev
            else:
                slots = align_slots(slots, ndev)
            self._slots_per_device = slots // ndev
            self.monitor = StepMonitor(n_hosts=ndev)
        else:
            self._slots_per_device = slots
            self.monitor = None
        self.slots = slots
        self.overlap_decode = (mesh is not None) if overlap_decode is None \
            else bool(overlap_decode)
        self.context = context
        # `is not None`, not truthiness: an empty AdmissionQueue is falsy
        # (len() == 0), and `or` would silently drop its depth bound
        self.queue = queue if queue is not None else AdmissionQueue()
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.on_straggler = on_straggler
        self.reporter = reporter
        self._straggler_flagged: Tuple[int, ...] = ()
        # shed attribution rides the queue's per-request hook (chained if
        # the caller installed one): the queue knows WHICH request each
        # shed dropped, so metrics and trace carry the victim's id
        self._user_on_shed = self.queue.on_shed
        self.queue.on_shed = self._queue_shed
        if mesh is not None:
            self.metrics.devices = int(mesh.shape['data'])
        self.photonic = photonic or (
            PhotonicAccountant(pipe.unet_cfg) if track_energy else None)
        self.noise_model = noise_model
        self.noise_seed = noise_seed
        self.quality_probe = quality_probe
        self.cache_interval = cache_interval
        self.exit_tol = exit_tol
        self.exit_patience = exit_patience
        self.exit_min_steps = max(2, exit_min_steps)
        cfg = pipe.unet_cfg
        self._sample_shape = (cfg.img_size, cfg.img_size, cfg.in_ch)
        # slot-axis sharding of every (slots, ...) buffer; None when
        # single-device.  Rebuilt (with the buffers and every jitted fn
        # whose out_shardings pin it) by elastic_resize.
        self._shard = None if mesh is None else named(mesh, PSpec('data'))
        self._place_weights()
        self.x = self._zeros_buf((slots,) + self._sample_shape)
        # previous-tick x0 predictions (the early-exit convergence signal)
        self.x0 = self._zeros_buf((slots,) + self._sample_shape)
        self._slot: List[Optional[_Active]] = [None] * slots
        self._pending: List[_Pending] = []   # decode-overlap in flight
        # requests displaced by an elastic shrink: (active, x row, x0 row)
        # host triples, re-admitted ahead of the queue as slots free
        self._parked: List[Tuple[_Active, np.ndarray, np.ndarray]] = []
        self._tick_s: Optional[float] = None  # measured service rate
        self._traj: Dict[int, np.ndarray] = {}
        self._wall_t0 = 0.0          # wall-clock origin (set by replay)
        self._probe_done = 0         # completed probe-eligible requests
        self._phase = 0              # shared refresh cadence position
        # precision machinery: policies and jitted steps are built lazily,
        # one step per (precision, guided) pair — plus, with caching on,
        # exactly one (refresh, skip) pair per (precision, guided) — each
        # closing over its frozen PrecisionPolicy; new policies never
        # disturb compiled ones
        self._policies: Dict[str, PrecisionPolicy] = {}
        self._steps: Dict[Tuple[str, bool], 'jax.stages.Wrapped'] = {}
        self._csteps: Dict[Tuple[str, bool, bool], 'jax.stages.Wrapped'] = {}
        self._zero_key = jax.random.PRNGKey(0)     # inert key, fp32/w8a8

        # slot-axis DeepCache buffers: the activation entering the last up
        # level, one row per slot (shape discovered by abstract evaluation
        # of the refresh pass — policies don't change it)
        self._cache_c = self._cache_u = None
        self._cache_row = None       # (row shape, dtype) for resize rebuilds
        if self.cache_interval > 1:
            cache_s = jax.eval_shape(
                lambda xx, tt: unet_apply_cached(
                    pipe.unet_params, cfg, xx, tt, None, True,
                    self.context, PrecisionPolicy.fp32()),
                jax.ShapeDtypeStruct((slots,) + self._sample_shape,
                                     jnp.float32),
                jax.ShapeDtypeStruct((slots,), jnp.int32))[1]
            self._cache_row = (tuple(cache_s.shape[1:]), cache_s.dtype)
            self._cache_c = self._zeros_buf(cache_s.shape, cache_s.dtype)
            if self.context is not None:
                # classifier-free guidance caches the unconditional
                # branch's deep features separately
                self._cache_u = self._zeros_buf(cache_s.shape, cache_s.dtype)

        self._build_helpers()

    def _zeros_buf(self, shape, dtype=jnp.float32):
        """A zero (slots, ...) buffer, placed sharded over the mesh's
        ``data`` axis when the engine is sharded."""
        buf = jnp.zeros(shape, dtype)
        if self._shard is not None:
            buf = jax.device_put(buf, self._shard)
        return buf

    def _place_weights(self) -> None:
        """The UNet / VAE weights and the conditioning every step takes as
        ARGUMENTS (never closed over: a closed-over array is compiled into
        the executable as a constant).  On a mesh they are replicated once
        per topology and the conditioning split by slot like the latents,
        so a tick moves no weights."""
        pipe = self.pipe
        self._unet_w, self._vae_w = pipe.unet_params, pipe.vae_params
        self._ctx = self.context
        if self.mesh is not None:
            self._unet_w, self._vae_w = jax.device_put(
                (self._unet_w, self._vae_w), named(self.mesh, PSpec()))
            if self._ctx is not None:
                self._ctx = jax.device_put(self._ctx, self._shard)

    def _build_helpers(self) -> None:
        """(Re)build the fixed-shape jitted helpers, each named so that
        it runs as ``jit_<name>``.  ``_place`` pins its output to the
        slot sharding so single-sample writes never gather the buffer
        onto one device.  Called at construction and again by
        ``elastic_resize`` — ``out_shardings`` captures the mesh, so a
        topology change must re-create the wrapped functions."""
        pipe = self.pipe
        shape = (1,) + self._sample_shape

        def init_noise(key):
            # exactly as ddim_sample: x = normal(split(key)[0], .)
            return jax.random.normal(jax.random.split(key)[0], shape)[0]

        def place_row(x, i, v):
            return x.at[i].set(v)

        def take_row(x, i):
            return x[i]

        def vae_decode(vp, z):
            return AE.vae_decode(vp, pipe.vae_cfg, z)

        self._init_noise = jax.jit(init_noise)
        if self._shard is not None:
            self._place = jax.jit(place_row, out_shardings=self._shard)
        else:
            self._place = jax.jit(place_row)
        self._take = jax.jit(take_row)
        self._decode = jax.jit(vae_decode) \
            if pipe.vae_params is not None else None

    # -- precision machinery ------------------------------------------------
    def _policy_for(self, name: str) -> PrecisionPolicy:
        """Resolve a request's precision name to this engine's policy."""
        if name not in self._policies:
            if name == 'fp32':
                pol = PrecisionPolicy.fp32()
            elif name == 'w8a8':
                cal = self.pipe.policy.calibration \
                    if self.pipe.policy.quantized else 'dynamic'
                pol = PrecisionPolicy.w8a8(calibration=cal)
            else:  # 'w8a8+noise' (request validation guarantees the name)
                pol = PrecisionPolicy.w8a8_noise(
                    model=self.noise_model, noise_seed=self.noise_seed)
            self._policies[name] = pol
        return self._policies[name]

    @staticmethod
    def _finish_step(sched, eps, x, x0p, t, t_prev, active):
        """Shared tail of every step variant: DDIM update + x0 tracking.

        Returns (x_out, x0_out, delta) where ``delta`` is the per-slot
        relative x0 movement ``||x0_t - x0_{t-1}|| / ||x0_{t-1}||``
        (RMS over sample dims; 0 for inactive slots) — the speculative
        early-exit convergence signal."""
        x_new, x0_new = samplers.ddim_step(sched, eps, x, t, t_prev,
                                           return_x0=True)
        axes = tuple(range(1, x.ndim))
        num = jnp.sqrt(jnp.mean((x0_new - x0p) ** 2, axis=axes))
        den = jnp.sqrt(jnp.mean(x0p ** 2, axis=axes)) + 1e-8
        delta = jnp.where(active, num / den, 0.0)
        mask = active.reshape((-1,) + (1,) * (x.ndim - 1))
        return (jnp.where(mask, x_new, x), jnp.where(mask, x0_new, x0p),
                delta)

    def _make_step(self, pol: PrecisionPolicy, use_guidance: bool):
        sched, cfg = self.pipe.sched, self.pipe.unet_cfg

        def step(x, x0p, t, t_prev, active, guidance, key, params, ctx):
            nkey = key if pol.noisy else None
            if use_guidance:
                # per-slot classifier-free guidance: blend against the
                # unconditional eps only for guided slots.  Under a noisy
                # policy the unconditional pass draws independent noise.
                ukey = jax.random.fold_in(key, 1) if pol.noisy else None
                eps_c = eps_fn(cfg, params, ctx, 0.0, pol, nkey)(x, t)
                eps_u = eps_fn(cfg, params, None, 0.0, pol, ukey)(x, t)
                g = guidance.reshape((-1,) + (1,) * (x.ndim - 1))
                eps = jnp.where(g > 0, eps_u + g * (eps_c - eps_u), eps_c)
            else:
                eps = eps_fn(cfg, params, ctx, 0.0, pol, nkey)(x, t)
            return self._finish_step(sched, eps, x, x0p, t, t_prev, active)
        return step

    def _make_cached_step(self, pol: PrecisionPolicy, use_guidance: bool,
                          refresh: bool):
        """DeepCache-phased step: ``refresh`` is STATIC (two jitted
        variants per (policy, guided) pair, matching the interval
        schedule).  The refresh variant rewrites the cache rows of the
        slots it ran; the skip variant reuses them via the shallow pass
        and leaves the buffers untouched."""
        sched, cfg = self.pipe.sched, self.pipe.unet_cfg

        def eval_cached(params, x, t, cache, context, nkey):
            return unet_apply_cached(params, cfg, x, t, cache, refresh,
                                     context, pol, noise_key=nkey)

        if use_guidance:
            def step(x, x0p, cache_c, cache_u, t, t_prev, active,
                     guidance, key, params, ctx):
                nkey = key if pol.noisy else None
                ukey = jax.random.fold_in(key, 1) if pol.noisy else None
                eps_c, new_c = eval_cached(params, x, t, cache_c, ctx, nkey)
                eps_u, new_u = eval_cached(params, x, t, cache_u, None, ukey)
                g = guidance.reshape((-1,) + (1,) * (x.ndim - 1))
                eps = jnp.where(g > 0, eps_u + g * (eps_c - eps_u), eps_c)
                x_out, x0_out, delta = self._finish_step(
                    sched, eps, x, x0p, t, t_prev, active)
                if refresh:
                    cm = active.reshape((-1,) + (1,) * (new_c.ndim - 1))
                    cache_c = jnp.where(cm, new_c, cache_c)
                    cache_u = jnp.where(cm, new_u, cache_u)
                return x_out, x0_out, delta, cache_c, cache_u
        else:
            def step(x, x0p, cache_c, t, t_prev, active, guidance, key,
                     params, ctx):
                nkey = key if pol.noisy else None
                eps, new_c = eval_cached(params, x, t, cache_c, ctx, nkey)
                x_out, x0_out, delta = self._finish_step(
                    sched, eps, x, x0p, t, t_prev, active)
                if refresh:
                    cm = active.reshape((-1,) + (1,) * (new_c.ndim - 1))
                    cache_c = jnp.where(cm, new_c, cache_c)
                return x_out, x0_out, delta, cache_c
        return step

    def _per_device(self, step, n_rows: int, n_out: int, name: str):
        """Jit a step whose first ``n_rows`` arguments and every output
        are slot-axis buffers, followed by (t, t_prev, active, guidance,
        key, params, ctx), as the program ``jit_<name>``.  On a mesh the
        step runs once per device over that device's slot rows
        (``shard_map``): Pallas kernels cannot be partitioned
        automatically, and rows are independent, so no collective is
        needed.  Weights and the key are replicated; the conditioning is
        split by slot like the latents.  Slot buffers are donated, so they
        stay resident across ticks."""
        step.__name__ = step.__qualname__ = name
        donate = tuple(range(n_rows))
        if self.mesh is None:
            return jax.jit(step, donate_argnums=donate)
        rows, rep = PSpec('data'), PSpec()
        step = jax.shard_map(
            step, mesh=self.mesh,
            in_specs=(rows,) * (n_rows + 4) + (rep, rep, rows),
            out_specs=(rows,) * n_out, check_vma=False)
        return jax.jit(step, donate_argnums=donate,
                       out_shardings=(self._shard,) * n_out)

    def _get_step(self, precision: str, guided: bool):
        k = (precision, guided)
        if k not in self._steps:
            pol = self._policy_for(precision)
            self._steps[k] = self._per_device(
                self._make_step(pol, guided), 2, 3,
                self.step_label(precision, guided))
        return self._steps[k]

    def _get_cached_step(self, precision: str, guided: bool, refresh: bool):
        k = (precision, guided, refresh)
        if k not in self._csteps:
            pol = self._policy_for(precision)
            n_rows = 4 if guided else 3
            self._csteps[k] = self._per_device(
                self._make_cached_step(pol, guided, refresh), n_rows,
                n_rows + 1, self.step_label(precision, guided, refresh))
        return self._csteps[k]

    def _tick_key(self, pol: PrecisionPolicy, tick_idx: int):
        """Per-tick analog-noise key: the policy's seed anchor folded with
        the tick index, so draws vary along every trajectory yet the whole
        serving run is deterministic under (seed, request sequence)."""
        if not pol.noisy:
            return self._zero_key
        return jax.random.fold_in(
            jax.random.PRNGKey(pol.noise_seed), tick_idx)

    # -- introspection -----------------------------------------------------
    @property
    def active_count(self) -> int:
        return sum(a is not None for a in self._slot)

    @property
    def busy(self) -> bool:
        return (self.active_count > 0 or len(self.queue) > 0
                or bool(self._pending) or bool(self._parked))

    @property
    def tick_s_estimate(self) -> Optional[float]:
        """Measured steady-state seconds per tick (None until
        ``measure_tick_s`` runs, settable so deployments can pin it).
        Feeds the admission-time SLO margin: a queued request whose
        deadline lands inside its own estimated service time is shed at
        admission instead of burning slot time on a guaranteed miss."""
        return self._tick_s

    @tick_s_estimate.setter
    def tick_s_estimate(self, value: Optional[float]) -> None:
        self._tick_s = None if value is None else float(value)

    def _service_margin_s(self, req: GenerationRequest) -> float:
        """Estimated service time were ``req`` admitted right now — the
        expiry margin.  One engine tick advances every in-flight request
        one step, so a request needs ~``steps`` ticks of residence.  0
        (expire only already-dead entries) until a tick estimate exists."""
        if self._tick_s is None:
            return 0.0
        return req.steps * self._tick_s

    def compile_stats(self) -> Dict[str, int]:
        """Per-jitted-function compile counts (cache sizes), keyed by
        program name (``step_label`` for the step variants, e.g.
        ``step_fp32_guided`` or ``step_w8a8_refresh``; ``init_noise``,
        ``place_row``, ``take_row``, ``vae_decode`` for the helpers).
        Constant after one warmup per served policy == zero
        recompilation."""
        out = {}
        for (pname, guided), fn in self._steps.items():
            out[self.step_label(pname, guided)] = self._cache_size(fn)
        for (pname, guided, refresh), fn in self._csteps.items():
            out[self.step_label(pname, guided, refresh)] = \
                self._cache_size(fn)
        for fn in (self._init_noise, self._place, self._take, self._decode):
            if fn is not None:
                out[fn.__name__] = self._cache_size(fn)
        return out

    @staticmethod
    def step_label(precision: str, guided: bool,
                   refresh: Optional[bool] = None) -> str:
        """The name of a step variant, e.g. ``step_fp32_guided`` or
        ``step_w8a8_noise_skip``: it runs as the program ``jit_<name>``
        (every step program, and no other, starts with ``jit_step``) and
        keys ``compile_stats`` and ``aot_warmup``."""
        name = 'step_' + precision.replace('+', '_')
        if refresh is not None:
            name += '_refresh' if refresh else '_skip'
        return name + ('_guided' if guided else '')

    @staticmethod
    def _cache_size(fn) -> int:
        try:
            return int(fn._cache_size())
        except Exception:                          # pragma: no cover
            return -1

    # -- observability -----------------------------------------------------
    #: queue shed causes -> the metrics ledger's reason names
    _SHED_REASONS = {'rejected': 'queue_full', 'evicted': 'deadline_evict',
                     'expired': 'expired'}

    def _queue_shed(self, reason: str, req: GenerationRequest,
                    now: float) -> None:
        """Per-request shed hook the ``AdmissionQueue`` fires: tally the
        cause in the metrics and attribute the shed to its request id in
        the trace."""
        self.metrics.record_shed(self._SHED_REASONS.get(reason, reason))
        if self.tracer.enabled:
            self.tracer.instant('shed', cat='queue', ts=now,
                                rid=req.request_id,
                                reason=self._SHED_REASONS.get(reason, reason),
                                trace_id=req.effective_trace_id)
        if self._user_on_shed is not None:
            self._user_on_shed(reason, req, now)

    def _slot_device(self, idx: int) -> Optional[int]:
        """Mesh device carrying slot row ``idx`` (None single-device)."""
        if self.mesh is None:
            return None
        return idx // self._slots_per_device

    def _poll_straggler(self):
        """Check the ``StepMonitor`` and, when its flagged-device set
        CHANGES, emit a straggler trace event and fire ``on_straggler``
        (edge-triggered so a persistent straggler doesn't refire every
        tick).  Returns the current report (None when clean)."""
        if self.monitor is None:
            return None
        report = self.monitor.check()
        flagged = tuple(report.slow_hosts) if report is not None else ()
        if flagged and flagged != self._straggler_flagged:
            self.tracer.instant('straggler', cat='engine',
                                slow_devices=list(flagged),
                                median_s=report.median_s,
                                threshold_s=report.threshold_s,
                                recommendation=report.recommendation)
            if self.on_straggler is not None:
                self.on_straggler(report)
        self._straggler_flagged = flagged
        return report

    # -- request flow ------------------------------------------------------
    def submit(self, req: GenerationRequest,
               now: Optional[float] = None) -> bool:
        now = time.perf_counter() if now is None else now
        with self.tracer.region('submit', rid=req.request_id):
            # sheds (rejected arrival / evicted entry) are recorded by the
            # queue's on_shed hook with the specific victim request
            ok = self.queue.submit(req, now)
            if ok:
                self.metrics.record_submit(now)
                if self.tracer.enabled:
                    self.tracer.instant('submit', cat='queue', ts=now,
                                        rid=req.request_id,
                                        steps=req.steps,
                                        precision=req.precision,
                                        trace_id=req.effective_trace_id)
            self.metrics.observe_queue_depth(len(self.queue))
        return ok

    def _trajectory(self, steps: int) -> np.ndarray:
        if steps not in self._traj:
            self._traj[steps] = samplers.ddim_timesteps(
                self.pipe.sched, steps)
        return self._traj[steps]

    def _cached_active(self) -> int:
        return sum(a is not None and a.cache_on for a in self._slot)

    def _unpark(self, idx: int) -> None:
        """Re-admit the oldest parked request into free slot ``idx``:
        restore its latent and x0 rows from the host copies.  DeepCache
        feature rows are NOT parked (their shape differs from the sample
        shape, and a resize changes their buffer anyway), so a
        cache-enabled request re-enters with ``force_refresh`` — its
        first tick back is a full pass that rewrites the rows."""
        a, hx, hx0 = self._parked.pop(0)
        self.x = self._place(self.x, jnp.int32(idx), jnp.asarray(hx))
        self.x0 = self._place(self.x0, jnp.int32(idx), jnp.asarray(hx0))
        if a.cache_on:
            a.force_refresh = True
        self._slot[idx] = a
        if self.tracer.enabled:
            self.tracer.instant('unpark', cat='queue',
                                rid=a.request.request_id, slot=idx,
                                device=self._slot_device(idx),
                                step_index=a.i)

    def _admit(self, now: float) -> int:
        """Fill free slots, parked requests first; returns how many
        requests entered a slot."""
        # expire whenever ANY queued entry carries a deadline — the SLO
        # is a property of the request, not of the shed policy, so a
        # dead request must never occupy a slot under 'reject-newest' or
        # an unbounded queue either.  The margin folds in the estimated
        # service time: a request that would only FINISH past its
        # deadline is equally dead at admission time.
        if getattr(self.queue, 'has_deadlines', False):
            # expired entries tally + trace through the queue's on_shed
            self.queue.expire(now, margin_s=self._service_margin_s)
        # parked (resize-displaced) requests re-enter ahead of the queue;
        # force_refresh lets them rejoin mid-cadence (a mixed tick)
        admitted = 0
        for idx in range(self.slots):
            if not self._parked:
                break
            if self._slot[idx] is None:
                self._unpark(idx)
                admitted += 1
        if self.cache_interval > 1:
            if self._cached_active() == 0:
                # nothing riding the cadence: re-anchor it so admission
                # is never delayed on an idle engine
                self._phase = 0
            if self._phase != 0 and self.queue.peek() is not None:
                # phase-aligned admission: hold queued requests until the
                # next refresh tick so every skip tick stays a whole-batch
                # shallow pass (the phase-alignment invariant)
                return admitted
        for idx in range(self.slots):
            if self._slot[idx] is not None:
                continue
            q = self.queue.pop()
            if q is None:
                break
            admitted += 1
            req = q.request
            interval = self.cache_interval if req.cache_interval is None \
                else req.cache_interval
            tol = self.exit_tol if req.exit_tol is None else req.exit_tol
            patience = self.exit_patience if req.exit_patience is None \
                else req.exit_patience
            self._slot[idx] = _Active(
                request=req, ts=self._trajectory(req.steps), i=0,
                submit_time=q.enqueue_time, start_time=now,
                cache_on=self.cache_interval > 1 and interval > 1,
                exit_tol=0.0 if tol is None else float(tol),
                exit_patience=patience)
            if self.tracer.enabled:
                self.tracer.instant('slot_assign', cat='queue', ts=now,
                                    rid=req.request_id, slot=idx,
                                    device=self._slot_device(idx),
                                    queue_wait_s=now - q.enqueue_time)
            noise = self._init_noise(jax.random.PRNGKey(req.seed))
            self.x = self._place(self.x, jnp.int32(idx), noise)
            # seed the x0 tracker with the slot's noise: the first delta
            # is meaningless and ignored (exit_min_steps >= 2)
            self.x0 = self._place(self.x0, jnp.int32(idx), noise)
        return admitted

    def _fp32_reference(self, req: GenerationRequest,
                        guided: bool) -> np.ndarray:
        """fp32 generation for the same seed/steps/guidance — the quality
        probe's reference image.  Context row 0 stands in for the
        engine's shared conditioning, which an unguided step applies too
        (it predicts the conditional noise alone)."""
        ctx = None if self.context is None else self.context[:1]
        ref = self.pipe.generate(
            jax.random.PRNGKey(req.seed), batch=1, steps=req.steps,
            context=ctx, guidance=req.guidance if guided else 0.0,
            policy=PrecisionPolicy.fp32())
        return np.asarray(ref[0])

    @staticmethod
    def _quality(image: np.ndarray, ref: np.ndarray):
        """(mse, psnr_db) of the served image vs the fp32 reference."""
        mse = float(np.mean((image.astype(np.float64) -
                             ref.astype(np.float64)) ** 2))
        rng = float(ref.max() - ref.min()) or 1.0
        psnr = math.inf if mse <= 0.0 else 10.0 * math.log10(rng * rng / mse)
        return mse, psnr

    def _begin_drain(self, idx: int, now: float,
                     wall_clock: bool = False,
                     early: bool = False) -> _Pending:
        """Dispatch a finished slot's VAE decode and free the slot.
        Dispatch only — no device sync — so under decode overlap the
        decode executes behind the next tick's UNet step and the slot is
        refillable immediately; ``_finish_drain`` pays the sync."""
        a = self._slot[idx]
        # an early-exit drain commits the CONVERGED x0 prediction — the
        # speculative clean image — instead of the partially-denoised x
        z = self._take(self.x0 if early else self.x, jnp.int32(idx))[None]
        if self._decode is not None:
            z = self._decode(self._vae_w, z)
        self._slot[idx] = None
        if early and self.tracer.enabled:
            self.tracer.instant('early_exit', cat='request', ts=now,
                                rid=a.request.request_id, slot=idx,
                                device=self._slot_device(idx),
                                steps_executed=a.i,
                                steps_requested=a.request.steps)
        return _Pending(active=a, z=z, now=now, wall_clock=wall_clock,
                        early=early, slot=idx)

    def _finish_drain(self, p: _Pending) -> GenerationResult:
        """Materialize a dispatched drain: device sync, latency stamp,
        energy + quality accounting, completion metrics."""
        a, z, now, wall_clock, early = (p.active, p.z, p.now,
                                        p.wall_clock, p.early)
        req = a.request
        pol = self._policy_for(req.precision)
        guided = req.guidance > 0.0 and self.context is not None
        energy_j = epb = 0.0
        if self.photonic is not None:
            # skip ticks are billed through the DeepCache workload
            # transform (shallow fraction of a full-UNet tick); early
            # exit pays only for the ticks that actually ran
            energy_j, epb = self.photonic.energy_evals(
                a.full_evals, a.cached_evals, guided,
                precision=req.precision)
        image = np.asarray(z[0])           # device sync: image materialized
        if wall_clock:
            # only now has the final step + decode actually executed
            now = time.perf_counter() - self._wall_t0
        # quality probe AFTER the latency stamp: the eager fp32 reference
        # is measurement apparatus, not served work.  Cached or
        # early-exited requests are probe-eligible at ANY precision —
        # their PSNR vs the full-step fp32 reference is the equal-quality
        # axis of the throughput frontier.
        mse = psnr = None
        reduced = early or a.cached_evals > 0
        if (pol.quantized or reduced) and self.quality_probe > 0:
            if self._probe_done % self.quality_probe == 0:
                mse, psnr = self._quality(
                    image, self._fp32_reference(req, guided))
            self._probe_done += 1
        res = GenerationResult(
            request_id=req.request_id, image=image,
            steps=req.steps, submit_time=a.submit_time,
            start_time=a.start_time, finish_time=now,
            energy_j=energy_j, epb_pj=epb,
            precision=req.precision, policy=pol,
            quality_psnr_db=psnr, quality_mse=mse,
            steps_executed=a.i, full_evals=a.full_evals,
            cached_evals=a.cached_evals, early_exit=early,
            trace_id=req.effective_trace_id)
        self.metrics.record_complete(res, slo_ms=req.slo_ms)
        if self.tracer.enabled:
            # the request span is stamped from the RESULT's own timing
            # fields, so trace latency == metrics latency exactly
            self.tracer.complete(
                'request', a.submit_time, now, cat='request',
                rid=req.request_id, slot=p.slot,
                device=self._slot_device(p.slot),
                trace_id=res.trace_id, precision=req.precision,
                steps_executed=a.i, full_evals=a.full_evals,
                cached_evals=a.cached_evals, early_exit=early,
                queue_wait_s=res.queue_delay_s, energy_j=energy_j,
                slo_ms=req.slo_ms)
        return res

    def _flush_pending(self, overlapped: bool) -> List[GenerationResult]:
        """Materialize every in-flight decode.  ``overlapped=True`` when
        a UNet step was dispatched between the decode dispatch and this
        sync (the decode actually hid behind compute)."""
        if not self._pending:
            return []
        pending, self._pending = self._pending, []
        if overlapped:
            self.metrics.record_overlapped_decode(len(pending))
        done = []
        for p in pending:
            with self.tracer.region('drain', rid=p.active.request.request_id,
                                    slot=p.slot, overlapped=overlapped):
                done.append(self._finish_drain(p))
        return done

    def tick(self, now: Optional[float] = None,
             wall_clock: Optional[bool] = None) -> List[GenerationResult]:
        """Admit (phase-aligned when caching) -> one mixed-timestep UNet
        step per (precision group, refresh|skip) pair -> drain finished
        and converged slots.

        ``wall_clock`` (default: `now` not given) makes drained results
        re-stamp their finish time after the device sync, so reported
        latencies include the final step + VAE decode.

        Under decode overlap a finished request's result surfaces on the
        FOLLOWING tick (its decode materializes after that tick's step
        is dispatched); an idle tick flushes the stragglers."""
        wall_clock = (now is None) if wall_clock is None else wall_clock
        now = time.perf_counter() - self._wall_t0 if now is None else now
        with self.tracer.region('tick', tick=self.metrics.ticks) as span:
            done = self._tick(now, wall_clock, span)
            span.set(drained=len(done))
        if self.reporter is not None:
            self.reporter.maybe_report(engine=self)
        return done

    def _tick(self, now: float, wall_clock: bool,
              span) -> List[GenerationResult]:
        tr = self.tracer
        t_tick0 = time.perf_counter()
        with tr.region('admit') as sp:
            sp.set(admitted=self._admit(now))
        if self.active_count == 0:
            # nothing to step: materialize leftover overlapped decodes
            # (no compute to hide behind, so not counted as overlapped)
            return self._flush_pending(overlapped=False)
        with tr.region('plan') as sp:
            caching = self.cache_interval > 1
            refresh_tick = self._phase == 0
            t = np.zeros(self.slots, np.int32)
            t_prev = np.full(self.slots, -1, np.int32)
            guidance = np.zeros(self.slots, np.float32)
            needs_refresh = np.ones(self.slots, bool)
            track_exit = False
            for idx, a in enumerate(self._slot):
                if a is None:
                    continue
                t[idx] = a.ts[a.i]
                t_prev[idx] = a.ts[a.i + 1] if a.i + 1 < len(a.ts) else -1
                guidance[idx] = a.request.guidance
                needs_refresh[idx] = ((not a.cache_on) or a.i == 0
                                      or refresh_tick or a.force_refresh)
                if a.exit_tol > 0.0 and a.i + 1 >= self.exit_min_steps:
                    track_exit = True
            plan = plan_tick(
                [a.request.precision if a is not None else None
                 for a in self._slot],
                needs_refresh, caching)
            tick_idx = self.metrics.ticks
            active_mask = np.zeros(self.slots, bool)
            for _, _, m in plan:
                active_mask |= m
            n_active = int(active_mask.sum())
            self.metrics.record_tick(
                n_active,
                full_slots=int((active_mask & needs_refresh).sum()),
                cached_slots=int((active_mask & ~needs_refresh).sum()))
            had_cached = self._cached_active() > 0
            t_d, tp_d = jnp.asarray(t), jnp.asarray(t_prev)
            sp.set(entries=len(plan))
        span.set(active=n_active)
        # one pre-compiled masked step per plan entry — (precision group,
        # refresh|skip) submask; donated latent/x0/cache buffers chain
        # call to call, so slots outside the running submask pass through
        # untouched
        delta_parts = []
        for pname, refresh, m in plan:
            g = np.where(m, guidance, 0.0).astype(np.float32)
            guided = self.context is not None and bool(g.any())
            with tr.region('dispatch', tick=tick_idx, precision=pname,
                           guided=guided, refresh=refresh,
                           slots=int(m.sum())):
                key = self._tick_key(self._policy_for(pname), tick_idx)
                m_d, g_d = jnp.asarray(m), jnp.asarray(g)
                if caching:
                    step_fn = self._get_cached_step(pname, guided,
                                                    refresh=refresh)
                    if guided:
                        (self.x, self.x0, d, self._cache_c,
                         self._cache_u) = step_fn(
                            self.x, self.x0, self._cache_c, self._cache_u,
                            t_d, tp_d, m_d, g_d, key, self._unet_w,
                            self._ctx)
                    else:
                        self.x, self.x0, d, self._cache_c = step_fn(
                            self.x, self.x0, self._cache_c,
                            t_d, tp_d, m_d, g_d, key, self._unet_w,
                            self._ctx)
                else:
                    step_fn = self._get_step(pname, guided)
                    self.x, self.x0, d = step_fn(
                        self.x, self.x0, t_d, tp_d, m_d, g_d, key,
                        self._unet_w, self._ctx)
            delta_parts.append((m, d))
        # decode overlap: decodes dispatched LAST tick materialize now,
        # behind the UNet step(s) just launched above
        done: List[GenerationResult] = self._flush_pending(overlapped=True)
        if self.metrics.first_tick_s is None:
            # cold-start probe: time-to-first-served-tick, device work
            # included (one extra sync, paid once per metrics object)
            jax.block_until_ready(self.x)
            self.metrics.record_first_tick(
                time.perf_counter() - self._created)
        # x0-convergence deltas: materialized (one tiny device sync) only
        # when some active slot is actually early-exit eligible this tick
        deltas = np.zeros(self.slots, np.float32)
        if track_exit:
            with tr.region('exit_sync'):
                for m, d in delta_parts:
                    dn = np.asarray(d)
                    deltas[m] = dn[m]
        for idx, a in enumerate(self._slot):
            if a is None:
                continue
            if needs_refresh[idx]:
                a.full_evals += 1
                a.force_refresh = False      # cache rows rewritten
            else:
                a.cached_evals += 1
            a.i += 1
            finished = early = False
            if a.i >= len(a.ts):
                finished = True
            elif a.exit_tol > 0.0 and a.i >= self.exit_min_steps:
                if deltas[idx] < a.exit_tol:
                    a.exit_streak += 1
                else:
                    a.exit_streak = 0
                if a.exit_streak >= a.exit_patience:
                    finished = early = True
            if not finished:
                continue
            if self.overlap_decode:
                # dispatch only: the image syncs behind the next tick,
                # in that tick's drain span
                self._pending.append(self._begin_drain(
                    idx, now, wall_clock=wall_clock, early=early))
                continue
            with tr.region('drain', rid=a.request.request_id, slot=idx,
                           overlapped=False):
                done.append(self._finish_drain(self._begin_drain(
                    idx, now, wall_clock=wall_clock, early=early)))
        if caching and had_cached:
            self._phase = (self._phase + 1) % self.cache_interval
        if self.monitor is not None:
            # one process drives every simulated device, so each shard
            # records the same wall tick time — the hook a real
            # deployment feeds per-device timings into (check() then
            # recommends the elastic_resize target)
            dt = time.perf_counter() - t_tick0
            for dev in range(int(self.mesh.shape['data'])):
                self.monitor.record(dev, dt)
            self._poll_straggler()
        if tr.enabled:
            tr.counter('occupancy', cat='engine', tick=tick_idx,
                       active=self.active_count, queued=len(self.queue))
        return done

    def run_until_idle(self, now: Optional[float] = None,
                       max_ticks: int = 100_000,
                       tick_dt: float = 0.0) -> List[GenerationResult]:
        """Drive ticks until queue and slots are empty.  With a logical
        clock (`now` given), each tick advances it by `tick_dt`."""
        results: List[GenerationResult] = []
        for _ in range(max_ticks):
            if not self.busy:
                return results
            results.extend(self.tick(now))
            if now is not None:
                now += tick_dt
        raise RuntimeError(f'engine still busy after {max_ticks} ticks')

    def replay(self, requests: List[GenerationRequest],
               max_ticks: int = 1_000_000,
               on_result=None) -> List[GenerationResult]:
        """Wall-clock replay of an arrival trace: each request is
        submitted once the serving clock passes its ``arrival_time``;
        the engine idles (sleeps) when nothing has arrived yet.
        ``on_result`` is called with each result as it completes —
        the hook deployments use to trigger a mid-replay
        ``elastic_resize`` (any results it flushes should be collected
        by the caller; they do not pass through this return value)."""
        pending = sorted(requests, key=lambda r: r.arrival_time)
        t0 = self._wall_t0 = time.perf_counter()
        # trace clock := replay serving clock, so trace timestamps and
        # GenerationResult timing fields agree exactly
        self.tracer.set_origin(t0)
        results: List[GenerationResult] = []
        for _ in range(max_ticks):
            now = time.perf_counter() - t0
            while pending and pending[0].arrival_time <= now:
                self.submit(pending.pop(0), now=now)
            if not self.busy:
                if not pending:
                    return results
                time.sleep(max(0.0, pending[0].arrival_time - now))
                continue
            # async dispatch overlaps host bookkeeping with device compute;
            # every drain materializes its image (device sync), so dispatch
            # can run ahead by at most one request's remaining steps
            batch = self.tick(now=time.perf_counter() - t0,
                              wall_clock=True)
            results.extend(batch)
            if on_result is not None:
                for res in batch:
                    on_result(res)
        raise RuntimeError('replay exceeded max_ticks')

    def elastic_resize(self, n_devices: Optional[int] = None,
                       devices=None, warm: bool = True,
                       precisions=('fp32',)) -> List[GenerationResult]:
        """Rebuild the slot buffer on a new ``('data',)`` mesh after
        devices drop or rejoin, preserving in-flight work.

        ``distributed.fault_tolerance.elastic_serving_plan`` sizes the
        new mesh and slot buffer at this engine's per-device slot budget
        (drop devices -> smaller buffer, never an overloaded survivor).
        In-flight latents and x0 trackers gather to the host and
        re-place onto the new buffer; when it is smaller, the overflow
        PARKS on the host and re-enters freed slots ahead of the queue.
        Every jitted function whose ``out_shardings`` pinned the old
        mesh is dropped and re-lowered for the new topology;
        ``warm=True`` pre-compiles the step variants via ``aot_warmup``
        (off the serving path — with a persistent compilation cache the
        re-lowering is a disk read).  Pending overlapped decodes flush
        first and their results are returned.  ``n_devices`` takes the
        first N visible devices; ``devices`` passes the surviving list
        explicitly."""
        if self.mesh is None:
            raise ValueError('elastic_resize needs a mesh-sharded engine '
                             '(construct with mesh=serving_mesh(...))')
        if n_devices is None and devices is None:
            raise ValueError('pass n_devices or an explicit device list')
        with self.tracer.region('elastic_resize') as span:
            return self._resize(n_devices, devices, warm, precisions, span)

    def _resize(self, n_devices, devices, warm, precisions,
                span) -> List[GenerationResult]:
        flushed = self._flush_pending(overlapped=False)
        from repro.launch.mesh import serving_mesh
        mesh = serving_mesh(n_devices=n_devices, devices=devices)
        old_ndev = int(self.mesh.shape['data'])
        new_ndev = int(mesh.shape['data'])
        _, _, new_slots = elastic_serving_plan(new_ndev,
                                               self._slots_per_device)
        # gather in-flight rows to the host before the old buffers die
        hx, hx0 = np.asarray(self.x), np.asarray(self.x0)
        live = [(a, hx[i], hx0[i]) for i, a in enumerate(self._slot)
                if a is not None]
        self.mesh = mesh
        self.slots = new_slots
        self._shard = named(mesh, PSpec('data'))
        if self.context is not None:
            # engine-wide conditioning: row 0 stands for every slot of the
            # new buffer (as it does for the quality probe's reference)
            self.context = jnp.broadcast_to(
                self.context[:1], (new_slots,) + self.context.shape[1:])
        self._place_weights()
        self.x = self._zeros_buf((new_slots,) + self._sample_shape)
        self.x0 = self._zeros_buf((new_slots,) + self._sample_shape)
        if self._cache_row is not None:
            row_shape, row_dtype = self._cache_row
            self._cache_c = self._zeros_buf((new_slots,) + row_shape,
                                            row_dtype)
            if self._cache_u is not None:
                self._cache_u = self._zeros_buf((new_slots,) + row_shape,
                                                row_dtype)
        self._slot = [None] * new_slots
        # in-flight work ahead of previously-parked work ahead of queue
        self._parked = live + self._parked
        self._steps.clear()
        self._csteps.clear()
        self._build_helpers()
        self.monitor = StepMonitor(n_hosts=new_ndev)
        self._straggler_flagged = ()
        self.metrics.record_resize(old_ndev, new_ndev)
        span.set(old_devices=old_ndev, new_devices=new_ndev,
                 slots=new_slots, parked=len(self._parked))
        for idx in range(self.slots):
            if not self._parked:
                break
            self._unpark(idx)
        if warm:
            self.aot_warmup(precisions=precisions)
        return flushed

    def warmup(self, precisions=('fp32',),
               cache_dir: Optional[str] = None) -> float:
        """Compile every code path (per-policy steps, place, take, decode
        — and, with caching on, the refresh AND skip variants) with
        throwaway requests so serving ticks never pay compile time.
        Pass every precision the engine will serve — e.g.
        ``warmup(('fp32', 'w8a8', 'w8a8+noise'))`` — one step compile per
        (policy, guided) pair (times the refresh/skip pair when caching),
        zero recompiles after.

        ``cache_dir`` routes every compilation through JAX's persistent
        on-disk cache first (``compile_cache.enable_persistent_cache``):
        the first (cold) warmup populates the directory, every later
        warmup in a fresh process loads executables from it instead of
        recompiling.  Returns wall seconds spent, also recorded in the
        metrics (``warmup_s``)."""
        if cache_dir is not None:
            from repro.serving.compile_cache import enable_persistent_cache
            enable_persistent_cache(cache_dir)
        t0 = time.perf_counter()
        saved_q, saved_m = self.queue, self.metrics
        saved_probe, saved_tracer = self.quality_probe, self.tracer
        # enough steps to cross a refresh boundary: compiles refresh+skip
        steps = 1 if self.cache_interval <= 1 else self.cache_interval + 1
        with saved_tracer.region('warmup',
                                 precisions=list(precisions)) as span:
            self.queue, self.metrics = AdmissionQueue(), ServingMetrics()
            self.quality_probe = 0      # no fp32 references for throwaways
            self.tracer = NULL_TRACER   # throwaways must not pollute traces
            try:
                for i, pname in enumerate(precisions):
                    self.submit(GenerationRequest(
                        request_id=-(2 * i + 1), seed=0, steps=steps,
                        exit_tol=0.0, precision=pname), now=0.0)
                    self.run_until_idle(now=0.0)
                    if self.context is not None:
                        # separately: the guided tick variant
                        self.submit(GenerationRequest(
                            request_id=-(2 * i + 2), seed=0, steps=steps,
                            guidance=7.5, exit_tol=0.0, precision=pname),
                            now=0.0)
                        self.run_until_idle(now=0.0)
            finally:
                self.queue, self.metrics = saved_q, saved_m
                self.quality_probe, self.tracer = saved_probe, saved_tracer
            dt = time.perf_counter() - t0
            span.set(seconds=dt)
        self.metrics.record_warmup(dt)
        trim_cache()    # enforce the persistent-cache size bound, if any
        return dt

    def step_variants(self, precisions=('fp32',)):
        """Every ``(precision, guided, refresh)`` step variant the given
        request mix can reach on this engine: guided variants exist only
        when the engine holds conditioning ``context``; refresh/skip
        variants only when DeepCache phasing is on (``refresh`` is None
        for the plain uncached step)."""
        guided_opts = (False, True) if self.context is not None else (False,)
        out = []
        for pname in precisions:
            for guided in guided_opts:
                if self.cache_interval > 1:
                    out.append((pname, guided, True))
                    out.append((pname, guided, False))
                else:
                    out.append((pname, guided, None))
        return out

    def aot_warmup(self, precisions=('fp32',),
                   cache_dir: Optional[str] = None) -> Dict[str, object]:
        """Ahead-of-time warmup: pre-lower and compile every step variant
        in ``step_variants(precisions)`` plus the fixed-shape helpers
        (init-noise, place, take, decode) WITHOUT executing a tick.

        With a persistent compilation cache enabled (``cache_dir`` or a
        prior ``enable_persistent_cache`` call) every executable lands on
        disk, so a restarted process — or this one's first served tick —
        finds a cache hit instead of paying XLA compilation.  Programs
        are lowered one at a time and compiled side by side on threads
        (the compiler runs outside the GIL; at paper-model widths each
        step variant takes minutes).  Returns ``{'variants': count,
        'seconds': wall, 'compile_s': {label: seconds}, 'compiled':
        {label: executable}}``, labels as in ``compile_stats``."""
        if cache_dir is not None:
            from repro.serving.compile_cache import enable_persistent_cache
            enable_persistent_cache(cache_dir)
        with self.tracer.region('aot_warmup') as span:
            out = self._aot_compile(precisions)
            span.set(variants=out['variants'], seconds=out['seconds'])
        return out

    def _aot_compile(self, precisions) -> Dict[str, object]:
        t0 = time.perf_counter()
        S = jax.ShapeDtypeStruct
        # sharded engines lower against slot-sharded buffer shapes, so
        # the persisted executables are exactly the ones serving uses
        sh = {} if self._shard is None else {'sharding': self._shard}
        xs = S((self.slots,) + self._sample_shape, jnp.float32, **sh)
        ti = S((self.slots,), jnp.int32)
        act = S((self.slots,), jnp.bool_)
        gd = S((self.slots,), jnp.float32)
        key = S(self._zero_key.shape, self._zero_key.dtype)
        w = (self._unet_w, self._ctx)
        lowered = {}
        for pname, guided, refresh in self.step_variants(precisions):
            label = self.step_label(pname, guided, refresh)
            if refresh is None:
                fn = self._get_step(pname, guided)
                lowered[label] = fn.lower(xs, xs, ti, ti, act, gd, key, *w)
            else:
                fn = self._get_cached_step(pname, guided, refresh)
                cs = S(self._cache_c.shape, self._cache_c.dtype, **sh)
                caches = (cs, cs) if guided else (cs,)
                lowered[label] = fn.lower(xs, xs, *caches, ti, ti, act, gd,
                                          key, *w)
        idx = S((), jnp.int32)
        sample = S(self._sample_shape, jnp.float32)
        lowered['init_noise'] = self._init_noise.lower(key)
        lowered['place_row'] = self._place.lower(xs, idx, sample)
        lowered['take_row'] = self._take.lower(xs, idx)
        if self._decode is not None:
            lowered['vae_decode'] = self._decode.lower(
                self._vae_w, S((1,) + self._sample_shape, jnp.float32))

        def build(item):
            label, low = item
            t = time.perf_counter()
            exe = low.compile()
            return label, exe, time.perf_counter() - t
        with ThreadPoolExecutor(len(lowered)) as pool:
            built = list(pool.map(build, lowered.items()))
        trim_cache()    # enforce the persistent-cache size bound, if any
        dt = time.perf_counter() - t0
        return {'variants': len(built), 'seconds': dt,
                'compile_s': {label: sec for label, _, sec in built},
                'compiled': {label: exe for label, exe, _ in built}}

    def measure_tick_s(self, steps: int = 4) -> float:
        """Steady-state wall seconds per engine tick at full slot
        occupancy (throwaway requests, metrics untouched) — the service
        capacity anchor for overload sizing: the engine completes
        ``slots / (steps * tick_s)`` requests/s.  Call after warmup so
        no compile time leaks into the measurement."""
        saved_q, saved_m = self.queue, self.metrics
        saved_probe, saved_tracer = self.quality_probe, self.tracer
        self.queue, self.metrics = AdmissionQueue(), ServingMetrics()
        self.quality_probe = 0
        self.tracer = NULL_TRACER       # throwaways must not pollute traces
        try:
            for i in range(self.slots):
                self.submit(GenerationRequest(request_id=-(100 + i),
                                              seed=i, steps=steps,
                                              exit_tol=0.0), now=0.0)
            t0 = time.perf_counter()
            self.run_until_idle(now=0.0)
            dt = time.perf_counter() - t0
            ticks = max(self.metrics.ticks, 1)
        finally:
            self.queue, self.metrics = saved_q, saved_m
            self.quality_probe, self.tracer = saved_probe, saved_tracer
        self._tick_s = dt / ticks    # feeds the admission SLO margin
        return self._tick_s
