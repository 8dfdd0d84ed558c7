"""The benchmark's cells at sizes a CPU test run can hold: every width
and count cut, the structure (guidance, cross-attention, VAE, open or
closed loop, limits) kept.  The reference is cheap at this size, so a
run compares up to 40 of the window's images instead of the cell's
sample, and a fault confined to some slots cannot slip past the draw."""
import copy
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(CHIP, '..', '..', 'src'))

import bench  # noqa: E402

SD = 't2i_unet_860m.t2i_batch'
DDPM = 'ddpm_cifar10_ch128.poisson'


def spec(name: str):
    s = copy.deepcopy(bench.cell_spec(name))
    s['limits']['requests'] = 40
    cfg, mix = s['config'], s['traffic']
    if name == SD:
        cfg['unet'].update(img_size=8, base_ch=32, ch_mults=[1, 2],
                           attn_resolutions=[4], context_dim=32, n_heads=4)
        cfg['vae'].update(img_size=16, base_ch=16, ch_mults=[1, 2])
        cfg['slots'] = 4
        mix.update(outstanding=8, ramp_completions=4,
                   steps=[[2, 6], [3, 8], [4, 3], [6, 3]])
    else:
        cfg['unet'].update(img_size=8, base_ch=32, ch_mults=[1, 2],
                           attn_resolutions=[4])
        cfg['slots'] = 8
        mix.update(rate_hz=300.0, ramp_s=0.5, steps=[[3, 12], [5, 7], [9, 1]])
    return s


def run(name: str, seed: int = 2 ** 31 + 7, seconds: float = 1.5,
        cache_dir=None, **kw):
    return bench.run(spec(name), seed, seconds, False, require_chip=False,
                     cache_dir=cache_dir, **kw)


def isolate_cache(tmp_path, monkeypatch):
    """Point the run's compile cache at ``tmp_path`` and switch the
    persistent cache back off after the test, so that a test run's other
    tests see the process as it was."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    saved = {k: getattr(jax.config, k) for k in (
        'jax_compilation_cache_dir',
        'jax_persistent_cache_min_entry_size_bytes',
        'jax_persistent_cache_min_compile_time_secs')}

    def restore():
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    return str(tmp_path), restore
