#!/usr/bin/env python3
"""The capacity of a cell's mix on the chip.

    python3 benchmarks/chip/capacity.py --workload <cell> --seed <n> \
        --slots 1024,2048 [--ticks 12]
    python3 benchmarks/chip/capacity.py --workload <cell> --seed <n> \
        --rates 38,41,44 [--seconds 30]

Each slot count or rate runs in a process of its own (one process per
chip).  ``--slots`` fills every slot with requests of the mix's longest
step count, times ``--ticks`` ticks and reports the wall time of a tick,
``peak_bytes_in_use`` and ``slots / (mean steps x tick)``: the engine
steps every slot at every tick, busy or not, so a tick's time does not
depend on how many are busy.  ``--rates`` runs the cell's open loop at
each rate (its ramp, then ``--seconds``) and reports the images served in
the window and the queue at its open and close: the highest rate at
which the queue does not grow is the capacity an open-loop cell's
``rate_hz`` is set below.  A closed loop of ``2 x slots`` requests that
all start at once does not measure it on many slots: they finish in
waves, and a window of a few turnovers reads the waves.  One JSON line
per slot count or rate.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import bench  # noqa: E402


def mean_steps(mix):
    n = sum(c for _, c in mix['steps'])
    return sum(s * c for s, c in mix['steps']) / n


def tick_time(spec, seed: int, ticks: int):
    import jax
    import weights
    cfg, mix = spec['config'], spec['traffic']
    slots = int(cfg['slots'])
    params = weights.make(cfg, seed)
    engine = bench.build_engine(cfg, params, seed + 1)
    loop = bench.Loop(engine, mix, seed)
    loop.warm()
    longest = max(s for s, _ in mix['steps'])
    for rid in range(slots):
        engine.submit(loop.Request(
            request_id=rid, seed=rid, steps=longest, guidance=loop.guidance,
            precision=loop.precision), now=time.perf_counter())
    for _ in range(3):
        engine.tick(wall_clock=True)
    jax.block_until_ready(engine.x)
    t0 = time.perf_counter()
    for _ in range(ticks):
        engine.tick(wall_clock=True)
    jax.block_until_ready(engine.x)
    tick = (time.perf_counter() - t0) / ticks
    return {'slots': slots, 'tick_s': tick,
            'capacity_per_s': slots / (mean_steps(mix) * tick),
            'peak_bytes': bench.memory_peak(jax.devices())}


def open_rate(spec, seed: int, seconds: float):
    """The open loop at the mix's ``rate_hz``, stopped at the window's
    close (no drain, no comparison)."""
    import weights
    cfg, mix = spec['config'], spec['traffic']
    params = weights.make(cfg, seed)
    engine = bench.build_engine(cfg, params, seed + 1)
    loop = bench.Loop(engine, mix, seed)
    loop.warm()
    bench.settled = lambda *a: True
    state = {}
    bench.run_open(loop, seconds,
                   lambda: state.update(queued_open=len(engine.queue)))
    t_open, t_close = loop.window
    served = sum(r.finish is not None and t_open <= r.finish <= t_close
                 for r in loop.records.values())
    return {'rate_hz': mix['rate_hz'], 'images_per_s': served / seconds,
            'queued_open': state['queued_open'],
            'queued_close': len(engine.queue),
            'tick_s': seconds / loop.window_ticks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    what = ap.add_mutually_exclusive_group(required=True)
    what.add_argument('--slots')
    what.add_argument('--rates')
    ap.add_argument('--ticks', type=int, default=12)
    ap.add_argument('--seconds', type=float, default=30.0)
    ap.add_argument('--one', action='store_true', help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    key = '--slots' if args.slots else '--rates'
    values = (args.slots or args.rates).split(',')
    if not args.one:
        rc = 0
        for v in values:
            argv1 = list(argv or sys.argv[1:])
            argv1[argv1.index(key) + 1] = v
            rc |= subprocess.call([sys.executable, __file__, *argv1, '--one'])
        return rc
    spec = copy.deepcopy(bench.cell_spec(args.workload))
    if bench.check_device(spec['chips']) is None:
        return 3
    bench.enable_cache(os.path.join(bench.ROOT, '.jax_cache'))
    sys.path.insert(0, os.path.join(bench.ROOT, 'src'))
    if args.slots:
        spec['config']['slots'] = int(values[0])
        res = tick_time(spec, args.seed, args.ticks)
    else:
        spec['traffic']['rate_hz'] = float(values[0])
        res = open_rate(spec, args.seed, args.seconds)
    print(json.dumps(dict(res, workload=args.workload)), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
