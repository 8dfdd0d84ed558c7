"""Per cent of the traced window with no operation on the device (the
open-loop cell, where a host gap lengthens every request)."""
from _common import idle_share


def read(run):
    return idle_share(run)
