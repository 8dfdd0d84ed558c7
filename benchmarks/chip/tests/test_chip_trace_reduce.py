"""``trace_reduce.py`` on a synthetic trace, and its reading of a real
(CPU-recorded) profiler file."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce as tr  # noqa: E402
from trace_reduce import Event  # noqa: E402

DEV = '/device:TPU:0'


def _synthetic():
    """A 100 ns window: a tick annotation over [0, 60), a sleep over
    [70, 100); device ops at [5, 20) and [15, 30) (overlapping), [40, 50)
    and [80, 90), inside one step program over [5, 50)."""
    host = tr.HOST_PLANE
    return [
        Event(host, 'python3', 'bench.window', 0, 100),
        Event(host, 'python3', 'bench.tick', 0, 60),
        Event(host, 'python3', 'bench.sleep', 70, 100),
        Event(host, 'python3', 'PjitFunction(step)', 1, 4),
        Event(DEV, tr.OPS_LINE, 'fusion.1', 5, 20),
        Event(DEV, tr.OPS_LINE, 'fused_gn_swish_kernel.3', 15, 30),
        Event(DEV, tr.OPS_LINE, 'fused_gn_swish_kernel.4', 40, 50),
        Event(DEV, tr.OPS_LINE, 'fusion.1', 80, 90),
        Event(DEV, tr.MODULES_LINE, 'jit_step(123)', 5, 50),
        Event(DEV, tr.MODULES_LINE, 'jit__lambda_(7)', 80, 90),
    ]


def test_short_names():
    assert tr.short('%fusion.127 = f32[16,64,64,340]{3,0,2,1} fusion(...)') \
        == 'fusion.127'
    assert tr.short('jit_step(123)') == 'jit_step(123)'


def test_union_and_gaps():
    assert tr.union([(5, 20), (15, 30), (40, 50)]) == [(5, 30), (40, 50)]
    assert tr.busy_ns([(5, 20), (15, 30), (40, 50)], 10, 45) == 25
    assert tr.gaps([(5, 30), (40, 50)], 0, 60) == [(0, 5), (30, 40),
                                                   (50, 60)]


def test_reduce_synthetic():
    evs = _synthetic()
    lo, hi = tr.window(evs, 'bench.window')
    red = tr.reduce(evs, lo, hi)
    assert red['planes'] == [DEV]
    assert red['window_ns'] == 100
    assert red['busy_ns'] == 25 + 10 + 10          # [5,30) [40,50) [80,90)
    assert red['per_op_ns'] == {'fusion.1': 25, 'fused_gn_swish_kernel.3': 15,
                                'fused_gn_swish_kernel.4': 10}
    assert red['op_count']['fusion.1'] == 2
    assert red['per_module_ns'] == {'jit_step': 45, 'jit__lambda_': 10}
    assert red['module_count'] == {'jit_step': 1, 'jit__lambda_': 1}
    # idle gaps, each labelled at its midpoint: [0,5) and [30,40) under
    # the tick, [50,80) under the window alone, [90,100) under the sleep
    labels = dict((lab, sec) for lab, sec in red['idle_by_label'])
    assert labels == pytest.approx({'bench.tick': 15e-9,
                                    'bench.window': 30e-9,
                                    'bench.sleep': 10e-9})
    assert red['idle_gaps'][0] == ['bench.window', pytest.approx(30e-9)]
    assert red['top_ops'][0] == ['fusion.1', pytest.approx(25e-9)]


def test_reduce_needs_a_device_plane():
    evs = [e for e in _synthetic() if not e.plane.startswith('/device')]
    with pytest.raises(ValueError):
        tr.reduce(evs, 0, 100)


def test_reads_a_recorded_trace(tmp_path):
    """A real profiler file, recorded here on the CPU: the benchmark's
    host annotations come back with their nesting and durations."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation('bench.window'):
        for _ in range(3):
            with jax.profiler.TraceAnnotation('bench.tick'):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    evs = tr.events(tr.find_xplane(str(tmp_path)))
    lo, hi = tr.window(evs, 'bench.window')
    ticks = [e for e in evs if e.name == 'bench.tick']
    assert len(ticks) == 3
    assert all(lo <= e.start_ns and e.end_ns <= hi for e in ticks)
    assert hi > lo
