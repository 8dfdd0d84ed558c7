"""Device milliseconds of the flash-attention kernel per execution of the
engine's step programs in the traced window.  Its events are the custom
calls named after the jitted kernel wrapper, ``flash_attention_kernel.<n>``
(``models/unet._mha`` sends self-attention of a multiple of 128 tokens,
longer than a head is wide, there); a program without them reads
nothing."""
from _common import step_programs

KERNEL = 'flash_attention_kernel'


def read(run):
    tr = run['trace']
    steps = step_programs(run)
    if tr is None or steps is None:
        return None
    names = [k for k in tr['per_op_ns'] if k.startswith(KERNEL)]
    if not sum(tr['op_count'][k] for k in names):
        return None
    return sum(tr['per_op_ns'][k] for k in names) / steps[1] / 1e6
