#!/usr/bin/env python3
"""Readings of a cell's comparison on the chip: the program's number and
the control's, over several seeds, each a whole run of the cell at its
own load.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> [--controls int8,fp8]

The runs share one process (set-up is long); each prints one JSON line
with the compared number and each control's (an operand rounding of the
configuration's model module, its ``OPERANDS``; default: the limits
file's ``control``).  The benchmark's own runs do not run the control:
this is how the limits in ``limits/<cell>.json`` were read.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import bench  # noqa: E402
import models  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--controls', default=None)
    args = ap.parse_args(argv)
    spec = bench.cell_spec(args.workload)
    controls = (args.controls or spec['limits']['control']).split(',')
    known = models.of(spec['config']).OPERANDS
    unknown = [c for c in controls if c not in known]
    if unknown:
        ap.error(f'no control {", ".join(unknown)}: the model module has '
                 f'{", ".join(known)}')
    for seed in (int(s) for s in args.seeds.split(',')):
        out = bench.run(spec, seed, args.seconds, False, controls=controls)
        if out is None:
            return 3
        print(json.dumps({'workload': args.workload, 'seed': seed,
                          'correct': out['correct'],
                          'compared': out['compared'],
                          'control': out['control']}), flush=True)
        gc.collect()
    return 0


if __name__ == '__main__':
    sys.exit(main())
