"""Median of slot assignment to image on the host over the requests due
inside the window."""
from _common import percentile, service_times


def read(run):
    return percentile(service_times(run['due_in_window']), 50)
