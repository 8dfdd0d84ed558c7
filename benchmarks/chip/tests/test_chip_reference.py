"""The plain reference against the served program, on the CPU at small
sizes, where the program runs in float32 throughout: the parameter
layout, one UNet pass, one decode, and whole runs of each cell."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_cells  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402


def _frozen(cls, d, **kw):
    return cls(**{k: tuple(v) if isinstance(v, list) else v
                  for k, v in d.items() if k in cls.__dataclass_fields__},
               **kw)


def _shapes(tree):
    return jax.tree_util.tree_map(lambda s: tuple(s.shape), tree)


@pytest.mark.parametrize('cell', [tiny_cells.SD, tiny_cells.DDPM])
def test_layout_is_the_programs(cell):
    """The tree the benchmark fills is the one the served UNet (and VAE
    decoder) initialises, leaf for leaf, at the cell's full widths."""
    from repro.models.autoencoder import VAEConfig, init_vae
    from repro.models.unet import UNetConfig, init_unet
    cfg = tiny_cells.bench.cell_spec(cell)['config']
    ucfg = _frozen(UNetConfig, cfg['unet'], name=cfg['name'])
    prog = jax.eval_shape(lambda k: init_unet(k, ucfg), jax.random.PRNGKey(0))
    ours = weights.shapes(cfg)
    assert _shapes(prog) == ours['unet']
    if cfg['vae'] is None:
        assert ours['vae'] is None
    else:
        vcfg = _frozen(VAEConfig, cfg['vae'])
        pv = jax.eval_shape(lambda k: init_vae(k, vcfg), jax.random.PRNGKey(0))
        assert {k: _shapes(pv[k]) for k in ours['vae']} == ours['vae']
    assert weights.count(ours['unet']) == cfg['params']['unet']


@pytest.mark.parametrize('context', [True, False])
def test_unet_pass_matches_program(context, monkeypatch):
    monkeypatch.setenv('REPRO_KERNELS', 'xla')
    from repro.models.unet import UNetConfig, unet_apply
    cfg = tiny_cells.spec(tiny_cells.SD)['config']
    u = cfg['unet']
    p = weights.make(cfg, 3)['unet']
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 4))
    t = jnp.array([999, 17], jnp.int32)
    ctx = jax.random.normal(jax.random.PRNGKey(2), (2, 77, 32)) \
        if context else None
    ucfg = _frozen(UNetConfig, u, name='tiny')
    prog = jax.jit(lambda *a: unet_apply(*a[:1], ucfg, *a[1:]))(p, x, t, ctx)
    ref = jax.jit(lambda *a: reference.unet(a[0], u, *a[1:]))(p, x, t, ctx)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_vae_decode_matches_program():
    from repro.models.autoencoder import VAEConfig, vae_decode
    cfg = tiny_cells.spec(tiny_cells.SD)['config']
    p = weights.make(cfg, 4)['vae']
    z = jax.random.normal(jax.random.PRNGKey(5), (1, 8, 8, 4))
    vcfg = _frozen(VAEConfig, cfg['vae'])
    prog = jax.jit(lambda p, z: vae_decode(p, vcfg, z))(p, z)
    ref = jax.jit(lambda p, z: reference.vae_decode(p, cfg['vae'], z))(p, z)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize('cell', [tiny_cells.SD, tiny_cells.DDPM])
def test_whole_run_is_correct(cell, tmp_path, monkeypatch):
    """A whole run of the cell, chip check skipped: every image compared
    agrees with the reference far inside the cell's limit, and the
    end-to-end metrics are all there."""
    cache, restore = tiny_cells.isolate_cache(tmp_path, monkeypatch)
    try:
        out = tiny_cells.run(cell, cache_dir=cache)
    finally:
        restore()
    assert out['correct'] is True
    assert out['failed'] == 0 and out['attempted'] > 0
    got = out['compared']['image_rel_l2_max']
    assert got['value'] < 1e-4 < got['limit']
    assert list(out)[-1] == 'compared'
    names = {m['name'] for m in tiny_cells.spec(cell)['end_to_end']}
    assert set(out['metrics']) == names
    assert all(m['value'] > 0 for m in out['metrics'].values())
