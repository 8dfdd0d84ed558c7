"""``flash_attention_ms`` on a synthetic trace: the device time of the
``flash_attention_kernel.<n>`` custom calls per step-program execution."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import bench  # noqa: E402
import trace_reduce as tr  # noqa: E402
from trace_reduce import Event  # noqa: E402

DEV = '/device:TPU:0'


def _trace(flash: bool):
    """Two guided steps of 40 ns and one decode in a 200 ns window.  With
    ``flash``, each step runs two kernel calls of 3 and 5 ns around its
    fusion; the decode never does."""
    evs = [Event(tr.HOST_PLANE, 'python3', 'bench.window', 0, 200)]
    for i, a in enumerate((10, 100)):
        b = a + 40
        evs.append(Event(DEV, tr.MODULES_LINE,
                         f'jit_step_fp32_guided({i})', a, b))
        if flash:
            evs += [Event(DEV, tr.OPS_LINE, 'flash_attention_kernel.7',
                          a, a + 3),
                    Event(DEV, tr.OPS_LINE, f'fusion.{i}', a + 3, b - 5),
                    Event(DEV, tr.OPS_LINE, 'flash_attention_kernel.9',
                          b - 5, b)]
        else:
            evs.append(Event(DEV, tr.OPS_LINE, f'fusion.{i}', a, b))
    evs += [Event(DEV, tr.MODULES_LINE, 'jit_vae_decode(2)', 150, 157),
            Event(DEV, tr.OPS_LINE, 'fusion.2', 150, 157)]
    return tr.reduce(evs, 0, 200)


def _read(red):
    return bench.reader('flash_attention_ms')({'trace': red})


def test_flash_attention_ms_per_step_program():
    """3 + 5 ns of kernel calls in each of two steps: 8 ns a step."""
    assert _read(_trace(flash=True)) == pytest.approx(8e-6)


def test_flash_attention_ms_nothing_to_read():
    """No trace, or steps with no kernel call in them (the einsum
    attention of earlier builds): no metric."""
    assert _read(None) is None
    assert _read(_trace(flash=False)) is None
