"""The model module of a configuration.

A configuration file names, under ``"model"``, the module of this
directory that knows its architecture: its parameter layout, its plain
reference, its control roundings and its FLOP count (``reference.py``'s
docstring sets out the interface).  Every part of the benchmark that
needs the model reaches it through ``of``, so a configuration of a new
architecture brings a module of its own and edits no file already here.
"""
from __future__ import annotations

import importlib

#: the names every model module has
INTERFACE = ('shapes', 'sample', 'context_rows', 'OPERANDS', 'image_flops',
             'gn_swish_calls')


def of(cfg):
    """The model module that configuration ``cfg`` names."""
    if 'model' not in cfg:
        raise ValueError(f"configuration {cfg.get('name')!r} names no "
                         "'model' module")
    mod = importlib.import_module(cfg['model'])
    missing = [name for name in INTERFACE if not hasattr(mod, name)]
    if missing:
        raise AttributeError(f"model module {cfg['model']!r} has no "
                             f"{', '.join(missing)}")
    return mod
