"""90th percentile, over every request due inside the window, of the
time from its due time to its image on the host."""
from _common import latencies, percentile


def read(run):
    return percentile(latencies(run['due_in_window']), 90)
