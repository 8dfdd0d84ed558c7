"""Share of its memory roofline that the fused GroupNorm+swish kernel
reaches: the HBM bytes its two ``pallas_call``s move (``flops.
gn_swish_bytes`` over every call of the step programs, all slot rows, as
the configuration's model module lists them in ``gn_swish_calls``) at
the chip's peak bandwidth, over their summed device time, in per cent.
Its events are the custom calls named after the jitted kernel wrapper,
``fused_gn_swish_kernel.<n>`` (statistics and normalise pass alike)."""
import flops
from _common import peak

KERNEL = 'fused_gn_swish_kernel'


def read(run):
    tr = run['trace']
    if tr is None:
        return None
    names = [k for k in tr['per_op_ns'] if k.startswith(KERNEL)]
    seen = sum(tr['op_count'][k] for k in names)
    ns = sum(tr['per_op_ns'][k] for k in names)
    if not seen or ns <= 0:
        return None
    cfg, calls_of = run['config'], run['model'].gn_swish_calls
    slots = int(cfg['slots'])
    calls = calls_of(cfg, slots)
    if run['guided']:
        calls += calls_of(cfg, slots, context=False)
    per_step = sum(flops.gn_swish_bytes(s) for s in calls)
    steps = seen / (2 * len(calls))
    t_min = steps * per_step / peak(run)['hbm_bytes_per_s']
    return 100.0 * t_min / (ns / 1e9)
