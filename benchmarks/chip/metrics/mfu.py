"""Useful FLOPs of the images completed in the window (the configuration's
model module's ``image_flops``: every step's UNet passes at batch 1, two
when guided, plus one decode) over the window's seconds times the chip's
bf16 peak, in per cent."""
from _common import peak


def read(run):
    if not run['finished']:
        return None
    image_flops = run['model'].image_flops
    work = sum(image_flops(run['config'], r.steps, run['guided'])
               for r in run['finished'])
    return 100.0 * work / (run['seconds'] * peak(run)['bf16_flops_per_s'])
