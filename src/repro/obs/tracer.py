"""Span/event tracer for the serving stack.

One ``Tracer`` records a serving run as a flat list of ``TraceEvent``
rows — instants (a request was submitted, a shed happened, a straggler
was flagged), complete spans (a whole tick, a step dispatch, a drained
image, warmup, a request's submit-to-finish lifetime) and counters
(occupancy per tick).
Timestamps ride the *serving clock*: ``now()`` is monotonic seconds
since the tracer's origin (``time.perf_counter`` based), and
``set_origin`` lets the engine pin that origin to its replay wall-clock
zero so trace timestamps and ``GenerationResult`` timing fields agree
exactly.  Events recorded with an explicit ``ts`` (e.g. a request span
stamped from the result's own submit/finish times) reconcile with
``ServingMetrics`` by construction.

``region`` is the one span primitive.  Every span, traced or not, is
also a ``jax.profiler.TraceAnnotation`` named ``<cat>.<name>`` (e.g.
``engine.drain``) carrying the span's int and string arguments as
metadata, so whenever a profiler is running the span lands on the
``/host:CPU`` plane of the same trace as the device ops, on their clock.
With no profiler running a span costs a few microseconds of host time.

Recording is ZERO-COST when disabled: the default engine tracer is the
module singleton ``NULL_TRACER`` whose ``enabled`` flag is False — hot
paths guard on that flag and never build event objects, every recording
method is a no-op, and its spans are profiler annotations alone.  An
enabled tracer appends one small dataclass per event; exporters
(``repro.obs.export``) turn the list into a JSONL structured log or a
Chrome/Perfetto ``trace_event`` timeline.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

#: Event categories used by the serving instrumentation.  Free-form —
#: exporters pass them through — but the engine sticks to this set.
CATEGORIES = ('queue', 'request', 'engine')

#: profiler names of spans, ``(cat, name) -> '<cat>.<name>'``, built once
#: per pair so a span formats no text
_LABELS: Dict[Tuple[str, str], str] = {}


def _meta(args: Dict[str, Any]) -> Dict[str, Any]:
    """The arguments a profiler annotation carries: ints (bools as 0/1)
    and strings."""
    return {k: int(v) if type(v) is bool else v for k, v in args.items()
            if type(v) in (int, str, bool)}


@dataclasses.dataclass
class TraceEvent:
    """One trace row.  ``ph`` follows the Chrome trace_event phases the
    exporter maps onto: ``'i'`` instant, ``'X'`` complete (has ``dur``),
    ``'C'`` counter (values live in ``args``)."""
    name: str
    cat: str
    ph: str
    ts: float                       # serving-clock seconds
    dur: float = 0.0                # seconds ('X' events only)
    rid: Optional[int] = None       # request id, when request-scoped
    slot: Optional[int] = None      # engine slot index, when slot-scoped
    device: Optional[int] = None    # mesh device index, when known
    tick: Optional[int] = None      # engine tick index, when tick-scoped
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        """Flat dict for the JSONL log (None-valued ids dropped)."""
        d = {'name': self.name, 'cat': self.cat, 'ph': self.ph,
             'ts': self.ts}
        if self.ph == 'X':
            d['dur'] = self.dur
        for k in ('rid', 'slot', 'device', 'tick'):
            v = getattr(self, k)
            if v is not None:
                d[k] = v
        if self.args:
            d['args'] = self.args
        return d


class Tracer:
    """Collects ``TraceEvent`` rows on a monotonic serving clock."""

    enabled = True

    def __init__(self):
        self._t0 = time.perf_counter()
        self.events: List[TraceEvent] = []

    # -- clock --------------------------------------------------------------
    def now(self) -> float:
        """Seconds since the trace origin (monotonic)."""
        return time.perf_counter() - self._t0

    def set_origin(self, perf_counter_t0: float) -> None:
        """Pin the trace origin to a ``time.perf_counter()`` reading —
        the engine passes its replay wall-clock zero so trace timestamps
        live on the same serving clock as request timing fields."""
        self._t0 = perf_counter_t0

    # -- recording ----------------------------------------------------------
    def instant(self, name: str, cat: str = 'engine',
                ts: Optional[float] = None, rid: Optional[int] = None,
                slot: Optional[int] = None, device: Optional[int] = None,
                tick: Optional[int] = None, **args) -> TraceEvent:
        e = TraceEvent(name=name, cat=cat, ph='i',
                       ts=self.now() if ts is None else ts,
                       rid=rid, slot=slot, device=device, tick=tick,
                       args=args)
        self.events.append(e)
        return e

    def complete(self, name: str, t0: float, t1: float,
                 cat: str = 'engine', rid: Optional[int] = None,
                 slot: Optional[int] = None, device: Optional[int] = None,
                 tick: Optional[int] = None, **args) -> TraceEvent:
        """A finished span ``[t0, t1]`` on the serving clock."""
        e = TraceEvent(name=name, cat=cat, ph='X', ts=t0,
                       dur=max(0.0, t1 - t0), rid=rid, slot=slot,
                       device=device, tick=tick, args=args)
        self.events.append(e)
        return e

    def counter(self, name: str, cat: str = 'engine',
                ts: Optional[float] = None, tick: Optional[int] = None,
                **values) -> TraceEvent:
        """A counter sample (numeric series, e.g. occupancy per tick)."""
        e = TraceEvent(name=name, cat=cat, ph='C',
                       ts=self.now() if ts is None else ts,
                       tick=tick, args=values)
        self.events.append(e)
        return e

    def region(self, name: str, cat: str = 'engine', **args) -> 'Span':
        """Span context manager: a profiler annotation ``<cat>.<name>``
        and, when this tracer records, a complete event on its clock.
        ``set`` on the span adds arguments known only at its end."""
        return Span(self, name, cat, args)

    # -- reading ------------------------------------------------------------
    def __len__(self) -> int:
        return len(self.events)

    def select(self, name: Optional[str] = None, cat: Optional[str] = None,
               ph: Optional[str] = None) -> List[TraceEvent]:
        """Events filtered by name/category/phase (None = any)."""
        return [e for e in self.events
                if (name is None or e.name == name)
                and (cat is None or e.cat == cat)
                and (ph is None or e.ph == ph)]

    def spans(self, name: Optional[str] = None,
              cat: Optional[str] = None) -> List[TraceEvent]:
        """Complete ('X') events, optionally filtered."""
        return self.select(name=name, cat=cat, ph='X')


class Span:
    """One ``Tracer.region``: entered, it opens the profiler annotation
    and reads the tracer clock; exited, it records the complete event
    (enabled tracers only) and closes the annotation."""

    __slots__ = ('_tracer', '_ann', '_t0', 'name', 'cat', 'args')

    def __init__(self, tracer: Tracer, name: str, cat: str,
                 args: Dict[str, Any]):
        label = _LABELS.get((cat, name))
        if label is None:
            label = _LABELS.setdefault((cat, name), cat + '.' + name)
        self._tracer, self.name, self.cat, self.args = tracer, name, cat, args
        self._ann = TraceAnnotation(label, **_meta(args))
        self._t0 = 0.0

    def __enter__(self) -> 'Span':
        self._ann.__enter__()
        if self._tracer.enabled:
            self._t0 = self._tracer.now()
        return self

    def __exit__(self, *exc) -> None:
        if self._tracer.enabled:
            self._tracer.complete(self.name, self._t0, self._tracer.now(),
                                  cat=self.cat, **self.args)
        self._ann.__exit__(*exc)

    def set(self, **args) -> None:
        """Arguments known only once the span's work is done."""
        self._ann.set_metadata(**_meta(args))
        self.args.update(args)


class NullTracer(Tracer):
    """No-op tracer: the zero-cost default.  ``enabled`` is False, so
    instrumented hot paths skip event construction entirely; the
    recording methods are inert for call sites that don't guard, and
    its regions are profiler annotations alone."""

    enabled = False

    def __init__(self):
        super().__init__()

    def instant(self, *a, **k) -> None:          # type: ignore[override]
        return None

    def complete(self, *a, **k) -> None:         # type: ignore[override]
        return None

    def counter(self, *a, **k) -> None:          # type: ignore[override]
        return None


#: Shared no-op singleton — the engine's default ``tracer``.
NULL_TRACER = NullTracer()
