"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device-op intervals, busy time and idle share over the
traced window, device time per operation and per compiled program, and
the longest idle gaps labelled by the benchmark's host annotation that
was open at the time.

A trace is reduced to ``Event(plane, line, name, start_ns, end_ns)``
tuples first (``events``), so the arithmetic below runs on any list of
them; the tests feed it a synthetic one.  On a TPU the device planes are
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per executed
operation, named by its HLO instruction (a Pallas kernel's custom call
takes the name of the jitted function that wraps it, e.g.
``fused_gn_swish_kernel.77``), and their
``XLA Modules`` line one per executed program (``jit_<name>(<id>)``).
Host annotations live on the ``/host:CPU`` plane.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Iterable, List, NamedTuple, Optional, Tuple

OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
HOST_PLANE = '/host:CPU'
DEVICE_PREFIX = '/device:'


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, '**', '*.xplane.pb'),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f'no .xplane.pb under {log_dir}')
    return paths[-1]


def short(name: str) -> str:
    """An XLA op event's name is its whole HLO instruction
    (``%fusion.12 = f32[...] fusion(...)``); keep the instruction's name."""
    return name.split(' = ', 1)[0].lstrip('%')


def events(path: str) -> List[Event]:
    """Every event of the trace at ``path``, op names shortened."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, short(ev.name),
                                 float(ev.start_ns), float(ev.end_ns)))
    return out


def device_planes(evs: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in evs if e.plane.startswith(DEVICE_PREFIX)
                   and e.line == OPS_LINE})


def select(evs, plane=None, line=None, prefix=None):
    return [e for e in evs if (plane is None or e.plane == plane)
            and (line is None or e.line == line)
            and (prefix is None or e.name.startswith(prefix))]


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def union(intervals) -> List[Tuple[float, float]]:
    """Merge overlapping (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_ns(intervals, lo, hi) -> float:
    return sum(b - a for a, b in union(clip(intervals, lo, hi)))


def gaps(intervals, lo, hi) -> List[Tuple[float, float]]:
    """Idle stretches of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for a, b in union(clip(intervals, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def window(evs, name: str) -> Optional[Tuple[float, float]]:
    """(start, end) of the host annotation ``name`` (the first one)."""
    for e in evs:
        if e.plane == HOST_PLANE and e.name == name:
            return e.start_ns, e.end_ns
    return None


def label_at(spans, t: float) -> str:
    """The innermost span of ``spans`` open at ``t``; ``spans`` is a list
    of (start, -end, name), sorted, so that of two spans that start
    together the outer one comes first."""
    i = bisect.bisect_right(spans, (t, float('inf'), '')) - 1
    best = None
    while i >= 0:
        a, neg_end, name = spans[i]
        if -neg_end >= t:
            best = name
            break
        i -= 1
    return best or 'none'


def reduce(evs: List[Event], lo: float, hi: float, annotations=('bench.',),
           top: int = 10):
    """Busy time, per-op and per-program device time, and labelled idle
    gaps over [lo, hi], averaged over the device planes."""
    planes = device_planes(evs)
    if not planes:
        raise ValueError('the trace has no device plane with XLA ops')
    busy = 0.0
    per_op = collections.Counter()
    op_count = collections.Counter()
    per_module = collections.Counter()
    module_count = collections.Counter()
    all_gaps = []
    for plane in planes:
        ops = select(evs, plane, OPS_LINE)
        ivs = [(e.start_ns, e.end_ns) for e in ops]
        busy += busy_ns(ivs, lo, hi)
        for e in ops:
            if e.end_ns > lo and e.start_ns < hi:
                per_op[e.name] += min(e.end_ns, hi) - max(e.start_ns, lo)
                op_count[e.name] += 1
        for e in select(evs, plane, MODULES_LINE):
            if e.start_ns >= lo and e.end_ns <= hi:
                name = e.name.split('(')[0]
                per_module[name] += e.dur_ns
                module_count[name] += 1
        all_gaps += gaps(ivs, lo, hi)
    n = len(planes)
    host = sorted((e.start_ns, -e.end_ns, e.name) for e in evs
                  if e.plane == HOST_PLANE
                  and any(e.name.startswith(a) for a in annotations))
    labelled = sorted(((b - a, label_at(host, (a + b) / 2))
                       for a, b in all_gaps), reverse=True)
    by_label = collections.Counter()
    for d, label in labelled:
        by_label[label] += d
    return {
        'planes': planes,
        'window_ns': hi - lo,
        'busy_ns': busy / n,
        'per_op_ns': {k: v / n for k, v in per_op.items()},
        'per_module_ns': {k: v / n for k, v in per_module.items()},
        'op_count': {k: v / n for k, v in op_count.items()},
        'module_count': {k: v / n for k, v in module_count.items()},
        'top_ops': [[k, v / n / 1e9] for k, v in per_op.most_common(top)],
        'idle_gaps': [[label, d / n / 1e9] for d, label in labelled[:top]],
        'idle_by_label': [[k, v / n / 1e9]
                          for k, v in by_label.most_common(top)],
    }
