"""The control — the reference with the operands of every convolution and
matmul rounded to the cell's control type (``limits/<cell>.json``:
``control``), one step below the bfloat16 operands of the configuration —
put in the program's place, at a size a CPU test run can hold: it has to
fail each cell's limit."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_cells  # noqa: E402


@pytest.mark.parametrize('cell', [tiny_cells.SD, tiny_cells.DDPM])
def test_control_fails_the_limit(cell, tmp_path, monkeypatch):
    name = tiny_cells.spec(cell)['limits']['control']
    cache, restore = tiny_cells.isolate_cache(tmp_path, monkeypatch)
    try:
        out = tiny_cells.run(cell, cache_dir=cache, controls=(name,))
    finally:
        restore()
    limit = out['compared']['image_rel_l2_max']['limit']
    assert out['correct'] is True
    assert out['control'][name]['image_rel_l2_max'] > limit
    assert list(out)[-1] == 'compared'
