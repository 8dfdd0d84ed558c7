"""Each configuration brings its model through the module it names
(``models.of``): the module has the whole interface, a configuration
that names no module or a module without part of the interface is
refused by name, a module that is new to the harness carries a whole run
and its metric readers, and both sides read the noise schedule from the
configuration."""
import copy
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_cells  # noqa: E402
import _common  # noqa: E402
import flops  # noqa: E402
import models  # noqa: E402
import reference  # noqa: E402
import spy_model  # noqa: E402

bench = tiny_cells.bench
BENCH = bench.load_json(os.path.join(bench.ROOT, 'BENCHMARK.json'))
V5E = 'TPU v5 lite'


@pytest.mark.parametrize('conf', BENCH['configs'], ids=lambda c: c['name'])
def test_configuration_names_a_whole_model(conf):
    cfg = bench.load_json(os.path.join(bench.ROOT, conf['file']))
    model = models.of(cfg)
    assert model.__name__ == cfg['model']
    for name in models.INTERFACE:
        assert hasattr(model, name), name
    assert all(callable(getattr(model, n)) for n in models.INTERFACE
               if n != 'OPERANDS')
    assert model.OPERANDS and all(map(callable, model.OPERANDS.values()))


def _bench_root(tmp_path, cfg):
    """A root whose BENCHMARK.json points the text-to-image configuration
    at ``cfg``, written to ``tmp_path``."""
    bench_json = copy.deepcopy(BENCH)
    conf = next(c for c in bench_json['configs']
                if c['name'] == 't2i_unet_860m')
    conf['file'] = 'config.json'
    (tmp_path / 'config.json').write_text(json.dumps(cfg))
    (tmp_path / 'BENCHMARK.json').write_text(json.dumps(bench_json))
    return str(tmp_path)


def test_configuration_without_model_names_its_file(tmp_path):
    cfg = bench.cell_spec(tiny_cells.SD)['config']
    del cfg['model']
    root = _bench_root(tmp_path, cfg)
    with pytest.raises(SystemExit, match='config.json: no "model"'):
        bench.cell_spec(tiny_cells.SD, root=root)
    with pytest.raises(ValueError, match="'t2i_unet_860m' names no 'model'"):
        models.of(cfg)


@pytest.mark.parametrize('missing', models.INTERFACE)
def test_model_without_part_of_the_interface_names_it(missing, tmp_path,
                                                      monkeypatch):
    name = f'partial_model_{missing}'
    (tmp_path / f'{name}.py').write_text(''.join(
        f'{n} = None\n' for n in models.INTERFACE if n != missing))
    monkeypatch.syspath_prepend(str(tmp_path))
    cfg = dict(bench.cell_spec(tiny_cells.SD)['config'], model=name)
    root = _bench_root(tmp_path, cfg)
    with pytest.raises(AttributeError, match=f"{name}' has no {missing}$"):
        bench.cell_spec(tiny_cells.SD, root=root)


def _run(spec, tmp_path, monkeypatch):
    cache, restore = tiny_cells.isolate_cache(tmp_path, monkeypatch)
    try:
        return bench.run(spec, 2 ** 31 + 15, 1.5, False, require_chip=False,
                         cache_dir=cache)
    finally:
        restore()


def test_a_new_model_module_carries_a_whole_run(tmp_path, monkeypatch):
    """A configuration that names ``spy_model`` (``reference`` with its
    calls counted) runs correct, and the run reaches the model through
    it alone: the weights' layout, the conditioning, every reference
    image and the FLOPs of ``mfu`` (read here in an untraced run, at the
    v5e's peak)."""
    spec = tiny_cells.spec(tiny_cells.SD)
    spec['config']['model'] = 'spy_model'
    spec['end_to_end'] += [m for m in spec['per_layer'] if m['name'] == 'mfu']
    peaks = bench.load_json(os.path.join(tiny_cells.CHIP, 'peaks.json'))
    monkeypatch.setattr(_common, 'peak', lambda run: peaks[V5E])
    spy_model.calls.clear()
    out = _run(spec, tmp_path, monkeypatch)
    assert out['correct'] is True
    calls = spy_model.calls
    assert calls['shapes'] == 1 and calls['context_rows'] == 1
    assert 1 <= calls['sample'] <= spec['limits']['requests']
    assert calls['image_flops'] >= 1
    assert out['metrics']['mfu']['value'] > 0


def test_metric_readers_reach_the_model_through_the_run():
    """``gn_swish_roofline`` takes its calls from the run's model module:
    on a synthetic trace of one guided step's kernel calls, each at the
    time the v5e's bandwidth allows, it reads 100%."""
    cfg = tiny_cells.spec(tiny_cells.SD)['config']
    calls = (flops.gn_swish_calls(cfg['unet'], cfg['slots'])
             + flops.gn_swish_calls(cfg['unet'], cfg['slots'], False))
    peaks = bench.load_json(os.path.join(tiny_cells.CHIP, 'peaks.json'))
    ns = 1e9 * sum(map(flops.gn_swish_bytes, calls)) \
        / peaks[V5E]['hbm_bytes_per_s']
    op = 'fused_gn_swish_kernel.7'
    run = {'trace': {'per_op_ns': {op: ns}, 'op_count': {op: 2 * len(calls)}},
           'config': cfg, 'model': spy_model, 'guided': True,
           'peaks': peaks, 'device_kind': V5E}
    spy_model.calls.clear()
    assert bench.reader('gn_swish_roofline')(run) == pytest.approx(100.0)
    assert spy_model.calls['gn_swish_calls'] == 2


@pytest.mark.parametrize('side', ['both', 'reference'])
def test_both_sides_read_the_schedule(side, tmp_path, monkeypatch):
    """``schedule.beta_T`` at 0.012 in the configuration: the served
    images and the reference's agree.  Read by the reference alone, with
    the engine at the configuration's 0.02, the run fails its limit."""
    spec = tiny_cells.spec(tiny_cells.DDPM)
    if side == 'both':
        spec['config']['schedule']['beta_T'] = 0.012
    else:
        plain = reference.sample

        def shifted(unet_p, vae_p, cfg, *args, **kw):
            cfg = copy.deepcopy(cfg)
            cfg['schedule']['beta_T'] = 0.012
            return plain(unet_p, vae_p, cfg, *args, **kw)
        monkeypatch.setattr(reference, 'sample', shifted)
    out = _run(spec, tmp_path, monkeypatch)
    got = out['compared']['image_rel_l2_max']
    if side == 'both':
        assert out['correct'] is True
        assert got['value'] < 1e-4 < got['limit']
    else:
        assert out['correct'] is False
        assert got['value'] > got['limit']


def test_a_schedule_kind_without_a_constructor_is_named():
    cfg = copy.deepcopy(tiny_cells.spec(tiny_cells.DDPM)['config'])
    cfg['schedule'] = {'kind': 'scaled_linear', 'beta_0': 0.00085,
                       'beta_T': 0.012}
    with pytest.raises(ValueError, match='no scaled_linear_schedule'):
        bench.build_engine(cfg, None, 0)
    with pytest.raises(ValueError, match="no 'scaled_linear' schedule"):
        reference.linear_alpha_bars(cfg)
