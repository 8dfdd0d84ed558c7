"""90th percentile of due time to slot assignment (the engine's
``start_time``) over the requests due inside the window."""
from _common import percentile


def read(run):
    return percentile([r.start - r.due for r in run['due_in_window']], 90)
