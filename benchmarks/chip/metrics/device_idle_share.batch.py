"""Per cent of the traced window with no operation on the device (the
closed-loop cell)."""
from _common import idle_share


def read(run):
    return idle_share(run)
