"""The per-layer readers of device programs on a synthetic trace with
the engine's program names: the step variants (``jit_step_<precision>
[_refresh|_skip][_guided]``) and the helpers (``jit_init_noise``,
``jit_place_row``, ``jit_take_row``, ``jit_vae_decode``)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import bench  # noqa: E402
import trace_reduce as tr  # noqa: E402
from trace_reduce import Event  # noqa: E402

DEV = '/device:TPU:0'


def _trace(decode='jit_vae_decode'):
    """Two guided steps of 40 ns, two decodes of 7 ns, one of each
    slot helper, in a 200 ns window."""
    mods = [('jit_init_noise', 0, 2), ('jit_place_row', 2, 4),
            ('jit_step_fp32_guided', 10, 50), ('jit_take_row', 50, 52),
            (decode, 52, 59), ('jit_step_fp32_guided', 100, 140),
            ('jit_take_row', 140, 142), (decode, 142, 149)]
    evs = [Event(tr.HOST_PLANE, 'python3', 'bench.window', 0, 200)]
    for i, (name, a, b) in enumerate(mods):
        evs.append(Event(DEV, tr.MODULES_LINE, f'{name}({i})', a, b))
        evs.append(Event(DEV, tr.OPS_LINE, f'fusion.{i}', a, b))
    return tr.reduce(evs, 0, 200)


def _read(metric, red):
    return bench.reader(metric)({'trace': red})


def test_vae_decode_ms():
    assert _read('vae_decode_ms', _trace()) == pytest.approx(7e-6)


def test_unet_step_ms_counts_step_programs_alone():
    assert _read('unet_step_ms', _trace()) == pytest.approx(40e-6)


@pytest.mark.parametrize('metric', ['vae_decode_ms', 'unet_step_ms'])
def test_nothing_to_read(metric):
    """No trace, or a trace whose programs run under other names (the
    anonymous ``jit__lambda_`` of earlier builds): no metric."""
    assert _read(metric, None) is None
    if metric == 'vae_decode_ms':
        assert _read(metric, _trace(decode='jit__lambda_')) is None
