"""A model module that is ``reference`` with every call of the interface
counted in ``calls``: a configuration that names it shows which parts of
a run reach the model through its configuration."""
import collections

import reference

calls = collections.Counter()

OPERANDS = reference.OPERANDS


def _counted(name):
    fn = getattr(reference, name)

    def counted(*args, **kw):
        calls[name] += 1
        return fn(*args, **kw)
    return counted


shapes = _counted('shapes')
sample = _counted('sample')
context_rows = _counted('context_rows')
image_flops = _counted('image_flops')
gn_swish_calls = _counted('gn_swish_calls')
