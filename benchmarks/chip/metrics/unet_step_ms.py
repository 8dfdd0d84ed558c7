"""Device milliseconds per execution of the engine's step programs
(``jit_step``: one tick's UNet passes and DDIM update) in the traced
window."""
from _common import step_programs


def read(run):
    got = step_programs(run)
    return None if got is None else got[0] / got[1] / 1e6
