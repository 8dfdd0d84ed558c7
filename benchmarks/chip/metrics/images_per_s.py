"""Images that reached the host inside the window, per second of it."""


def read(run):
    return len(run['finished']) / run['seconds']
