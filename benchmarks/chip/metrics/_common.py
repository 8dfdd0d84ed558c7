"""Arithmetic shared by the metric readers (not a metric itself)."""
from __future__ import annotations

import math


def percentile(values, p: float):
    """Nearest-rank percentile; None for no values."""
    vals = sorted(values)
    if not vals:
        return None
    idx = min(len(vals) - 1, max(0, int(round(p / 100.0 * (len(vals) - 1)))))
    return vals[idx]


def latencies(recs):
    """Due time to image on the host of each request; infinite for one
    still running when the run stopped (it stops only once the
    percentiles read are fixed: ``bench.settled``)."""
    return [math.inf if r.finish is None else r.finish - r.due for r in recs]


def service_times(recs):
    """Slot assignment to image on the host of each request; infinite for
    one still running."""
    return [math.inf if r.finish is None else r.finish - r.start
            for r in recs]


def idle_share(run):
    """Per cent of the traced window in which no operation ran on the
    device."""
    tr = run['trace']
    if tr is None or tr['window_ns'] <= 0:
        return None
    return 100.0 * (1.0 - tr['busy_ns'] / tr['window_ns'])


def step_programs(run):
    """(device ns, executions) of the engine's step programs in the
    traced window."""
    tr = run['trace']
    if tr is None:
        return None
    ns = sum(v for k, v in tr['per_module_ns'].items()
             if k.startswith('jit_step'))
    n = sum(v for k, v in tr['module_count'].items()
            if k.startswith('jit_step'))
    return (ns, n) if n else None


def peak(run):
    return run['peaks'][run['device_kind']]
