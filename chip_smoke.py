#!/usr/bin/env python3
"""Serve ``sd_v1_4`` at its published widths on a TPU through the serving
engine, and check what comes out.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the slot-sharded engine, four chips

Run it from the root of a checkout, on a machine with a TPU.  Everything
runs in this one process (a chip belongs to one process).  It exits
non-zero and prints no result when JAX finds no TPU, when
``REPRO_KERNELS`` forces a mode other than ``pallas``, or when any check
fails; nothing is caught.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Compiles go to the persistent cache (``$JAX_COMPILATION_CACHE_DIR``, else
``.jax_cache`` in the checkout), so a second run loads them.

One chip: ``launch.serve.build_engine`` — the setup that ``serve
--diffusion --model sd_v1_4`` runs — builds sd_v1_4 with VAE_512 from
seeded weights on 4 slots.  Every step variant is compiled ahead of time,
then 6 requests at 10 DDIM steps are served: fp32 and w8a8, each guided
(scale 7.5) and unguided.  Checks: every drained request decodes to a
finite (512, 512, 3) image; no step recompiles during the replay; every
compiled step holds a Pallas kernel (``tpu_custom_call``); one fp32 image
matches a standalone ``DiffusionPipeline.generate`` of the same seed and
steps within ``REL_L2_BOUND``; the quality probe reports the w8a8 PSNR.

Four chips (``--chips 4``), and nothing else: the engine sharded over 4
chips at 2 slots each serves 8 fp32 requests, and one ``elastic_resize``
to 2 chips happens mid-replay with requests in flight; every request must
complete, and each image must match the one-chip engine (2 slots, same
weights, same requests) within ``SHARD_BOUND``.

Times, compile seconds and ``peak_bytes_in_use`` are printed for
information; they are not device metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, 'src'))

MODEL = 'sd_v1_4'
STEPS = 10
SLOTS = 4
REQUESTS = 6

#: Bound on ||served - standalone|| / ||standalone|| for one fp32 image.
#: Slot rows are independent and both programs run the same weights at
#: the TPU's default matmul precision, which rounds matmul operands to
#: bf16 (relative step 2^-8 = 3.9e-3).  The batch-4 engine step and the
#: batch-1 standalone loop fuse and accumulate in different orders, so
#: operands round to neighbouring bf16 values, and 10 steps through the
#: random-weight UNet carry that drift to the image: 6.3e-3 on a v5e.
#: 2e-2 is about five bf16 steps.  A wrong seed or timestep gives an
#: unrelated image (relative error above 1: the script prints one such
#: pair).
REL_L2_BOUND = 2e-2

#: Bound on ||sharded - one chip|| / ||one chip|| for every image the
#: 4-chip mesh serves, against the same request on the one-chip engine.
#: Slot rows are independent, but the sharded step is a different
#: program (``shard_map`` over the mesh) from the one-chip ``jit``, so it
#: fuses and rounds its bf16 matmul operands at other points: the same
#: drift as in ``REL_L2_BOUND``, with the same bound.  On a v5e the
#: largest absolute pixel difference came to 1.29e-2, which a per-pixel
#: bound cannot tell from a wrong image; a request paired with another
#: request's image (printed) sits above 1.
SHARD_BOUND = REL_L2_BOUND

#: What a compiled step's text holds where a Pallas kernel was lowered.
KERNEL_MARK = 'tpu_custom_call'


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check_images(results, n: int, shape) -> None:
    import numpy as np
    assert len(results) == n, f'{len(results)} results for {n} requests'
    for r in results:
        assert r.image.shape == shape, (r.request_id, r.image.shape)
        assert np.isfinite(r.image).all(), f'request {r.request_id}: ' \
            'non-finite image'


def in_thread(fn):
    """Start ``fn`` on a thread; the returned ``join`` re-raises what it
    raised.  The thread shares this process's hold on the chip."""
    box = {}

    def run():
        try:
            box['value'] = fn()
        except BaseException as e:          # handed to join(), not lost
            box['error'] = e
    th = threading.Thread(target=run)
    th.start()

    def join():
        th.join()
        if 'error' in box:
            raise box['error']
        return box['value']
    return join


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get('peak_bytes_in_use', 'not reported'))


def one_chip() -> None:
    """Serve the fp32 / w8a8 x guided / unguided mix on one chip."""
    import jax
    from repro.launch import serve
    from repro.models.layers import count_params
    t0 = time.perf_counter()
    engine = serve.build_engine(MODEL, slots=SLOTS, quality_probe=2)
    pipe = engine.pipe
    log(f'[build] {MODEL}: {count_params(pipe.unet_params) / 1e6:.2f}M'
        f' UNet + {count_params(pipe.vae_params) / 1e6:.2f}M VAE '
        f'parameters, {engine.slots} slots, context '
        f'{tuple(engine.context.shape)}: '
        f'{time.perf_counter() - t0:.1f}s')
    trace = serve.poisson_trace(REQUESTS, 1e6, STEPS,
                                precision=('fp32', 'w8a8'),
                                guidance=serve.GUIDANCE)
    ref_req = trace[0]
    assert ref_req.precision == 'fp32' and ref_req.guidance == 0.0

    # the standalone reference compiles and runs while the engine's step
    # variants compile; the quality probe's fp32 reference of an unguided
    # request is the same program.  Row 0 of the engine-wide conditioning
    # is what an unguided step applies.
    t0 = time.perf_counter()
    reference = in_thread(lambda: jax.block_until_ready(pipe.generate(
        jax.random.PRNGKey(ref_req.seed), batch=1, steps=STEPS,
        context=engine.context[:1]))[0])
    info = engine.aot_warmup(precisions=('fp32', 'w8a8'))
    for label, sec in info['compile_s'].items():
        log(f'[compile] {label}: {sec:.1f}s')
    log(f'[compile] {info["variants"]} programs in {info["seconds"]:.1f}s')
    for label, exe in info['compiled'].items():
        if label.startswith('step_'):
            assert KERNEL_MARK in exe.as_text(), \
                f'{label}: no Pallas kernel in the compiled step'
    log('[compile] every step variant holds a tpu_custom_call')
    del info
    warm_s = engine.warmup(precisions=('fp32', 'w8a8'))
    standalone = reference()
    log(f'[warmup] {warm_s:.1f}s; ready after '
        f'{time.perf_counter() - t0:.1f}s')

    before = engine.compile_stats()
    log(f'[compile_stats] {before}')
    t0 = time.perf_counter()
    results = engine.replay(trace)
    log(f'[replay] {len(results)} requests in '
        f'{time.perf_counter() - t0:.1f}s')
    after = engine.compile_stats()
    assert after == before, f'recompiled during replay: {after}'
    log(f'[compile_stats] unchanged: {after}')

    img = pipe.vae_cfg.img_size
    check_images(results, REQUESTS, (img, img, pipe.vae_cfg.in_ch))
    combos = {(r.precision, trace[r.request_id].guidance > 0)
              for r in results}
    assert combos == {(p, g) for p in ('fp32', 'w8a8')
                      for g in (False, True)}, combos
    log(f'[serve] every image finite, shape {(img, img, 3)}; served '
        f'{sorted(combos)}')

    by_id = {r.request_id: r for r in results}
    err = rel_l2(by_id[ref_req.request_id].image, standalone)
    other = next(r for r in results if r.request_id != ref_req.request_id
                 and r.precision == 'fp32' and not trace[r.request_id].guidance)
    log(f'[reference] request {ref_req.request_id} vs standalone generate: '
        f'rel L2 {err:.3e} (bound {REL_L2_BOUND:g}); request '
        f'{other.request_id} (another seed) vs the same: '
        f'{rel_l2(other.image, standalone):.3e}')
    probed = [r for r in results if r.quality_psnr_db is not None]
    for r in probed:
        log(f'[probe] request {r.request_id} {r.precision} vs fp32: '
            f'PSNR {r.quality_psnr_db:.2f} dB, MSE {r.quality_mse:.3e}')
    log(f'[memory] peak_bytes_in_use {peak_bytes(jax.devices()[0])}')
    assert err < REL_L2_BOUND, err
    assert probed, 'the quality probe ran on no request'


def four_chips() -> None:
    """The slot-sharded engine on 4 chips against the one-chip engine."""
    import jax
    import numpy as np
    from repro.launch import serve
    n = len(jax.devices())
    assert n >= 4, f'--chips 4 needs 4 devices, found {n}'
    t0 = time.perf_counter()
    single = serve.build_engine(MODEL, slots=2, quality_probe=0)
    sharded = serve.build_engine(MODEL, devices=4, slots_per_device=2,
                                 pipe=single.pipe, quality_probe=0)
    log(f'[build] {MODEL} on 1 chip x 2 slots and 4 chips x 2 slots: '
        f'{time.perf_counter() - t0:.1f}s')
    # half the requests finish early, so the resize they trigger finds
    # the other half in flight
    trace = [dataclasses.replace(r, steps=STEPS // 2) if i < 4 else r
             for i, r in enumerate(serve.poisson_trace(8, 1e6, STEPS))]

    # the one-chip engine serves the same requests alongside, on chip 0
    t0 = time.perf_counter()
    one_chip_results = in_thread(lambda: single.replay(trace))
    state = {'resized': False, 'in_flight': 0, 'flushed': []}

    def on_result(res):
        if not state['resized']:
            state['resized'] = True
            state['in_flight'] = sharded.active_count
            state['flushed'] = sharded.elastic_resize(n_devices=2,
                                                      warm=False)
            log(f'[resize] 4 -> 2 chips after request {res.request_id}: '
                f'{sharded.slots} slots, {state["in_flight"]} requests in '
                f'flight')
    t0 = time.perf_counter()
    results = sharded.replay(trace, on_result=on_result)
    results += state['flushed']
    log(f'[sharded] {len(results)} requests in '
        f'{time.perf_counter() - t0:.1f}s (compiles included)')
    ref = {r.request_id: r for r in one_chip_results()}
    log(f'[one chip] {len(ref)} requests in '
        f'{time.perf_counter() - t0:.1f}s (compiles included)')
    assert state['resized'] and state['in_flight'] > 0, state
    img = single.pipe.vae_cfg.img_size
    check_images(results, len(trace), (img, img, 3))
    err = max(rel_l2(r.image, ref[r.request_id].image) for r in results)
    pixel = max(float(np.max(np.abs(r.image - ref[r.request_id].image)))
                for r in results)
    other = rel_l2(results[0].image,
                   ref[(results[0].request_id + 1) % len(trace)].image)
    log(f'[sharded] every request completed; max rel L2 sharded vs one '
        f'chip {err:.3e} (bound {SHARD_BOUND:g}), max |pixel difference| '
        f'{pixel:.3e}; request {results[0].request_id} vs another '
        f'request: {other:.3e}')
    assert err < SHARD_BOUND, err
    for i, dev in enumerate(jax.devices()[:4]):
        log(f'[memory] device {i} peak_bytes_in_use {peak_bytes(dev)}')


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--chips', type=int, default=1, choices=(1, 4),
                    help='1: serve the precision / guidance mix on one '
                         'chip; 4: only the slot-sharded engine on four')
    args = ap.parse_args()
    try:
        import repro.launch.serve  # noqa: F401
    except ImportError as e:
        raise SystemExit(f'chip_smoke.py: cannot import the repro package '
                         f'from {HERE}/src ({e}); run it from a checkout')
    import jax
    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        raise SystemExit(f'chip_smoke.py: JAX found no TPU (platform '
                         f'{dev.platform!r})')
    forced = os.environ.get('REPRO_KERNELS')
    if forced and forced != 'pallas':
        raise SystemExit(f'chip_smoke.py: REPRO_KERNELS={forced} would '
                         'bypass the Pallas kernels on the TPU')
    from repro.serving.compile_cache import (default_cache_dir,
                                             enable_persistent_cache)
    cache = enable_persistent_cache(default_cache_dir())
    log(f'[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; '
        f'compile cache {cache}')
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    log(f'[done] {time.perf_counter() - t0:.1f}s')
    print(json.dumps({'ok': True, 'device': {
        'platform': dev.platform, 'kind': dev.device_kind,
        'count': len(jax.devices())}}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
