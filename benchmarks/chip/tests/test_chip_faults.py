"""Whole runs of each cell, chip check skipped, with the served path
broken underneath: ``correct`` has to come out false for every fault the
cell can have.  (One chip: no exchange between chips to leave out.)"""
import os
import sys

import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_cells  # noqa: E402


def _unchanged(orig):
    """Every step returns the slots' state as it was."""
    def make(self, pol, guided):
        def step(x, x0p, *rest):
            return x, x0p, jnp.zeros(x.shape[:1], x.dtype)
        return step
    return '_make_step', make


def _half_batch(orig):
    """Only the first half of the slot rows is stepped; the rest keep
    their state."""
    def make(self, pol, guided):
        real = orig(self, pol, guided)

        def step(x, x0p, *rest):
            xn, x0n, d = real(x, x0p, *rest)
            keep = (jnp.arange(x.shape[0]) < x.shape[0] // 2).reshape(
                (-1,) + (1,) * (x.ndim - 1))
            return jnp.where(keep, xn, x), jnp.where(keep, x0n, x0p), d
        return step
    return '_make_step', make


def _altered(orig):
    """The finished latent is altered where it is taken out of its slot:
    a wave along its rows (one period over the height) of 10% of its RMS
    is added."""
    def build(self):
        orig(self)
        take = self._take

        def altered(x, i):
            v = take(x, i)
            rows = jnp.arange(v.shape[0], dtype=v.dtype) / v.shape[0]
            wave = jnp.cos(2 * jnp.pi * rows)[:, None, None]
            return v + 0.1 * jnp.sqrt(jnp.mean(v * v)) * wave
        self._take = altered
    return '_build_helpers', build


FAULTS = {'unchanged_state': _unchanged, 'half_batch': _half_batch,
          'altered_answer': _altered}


@pytest.mark.parametrize('fault', sorted(FAULTS))
@pytest.mark.parametrize('cell', [tiny_cells.SD, tiny_cells.DDPM])
def test_fault_is_caught(cell, fault, tmp_path, monkeypatch):
    from repro.serving.engine import ContinuousBatchingEngine as E
    name = '_make_step' if fault != 'altered_answer' else '_build_helpers'
    attr, broken = FAULTS[fault](getattr(E, name))
    monkeypatch.setattr(E, attr, broken)
    cache, restore = tiny_cells.isolate_cache(tmp_path, monkeypatch)
    try:
        out = tiny_cells.run(cell, cache_dir=cache)
    finally:
        restore()
    got = out['compared']['image_rel_l2_max']
    assert out['correct'] is False, got
    assert got['value'] > got['limit']
