"""Process start to window open: weights, engine, compiles or cache
loads, warm-up request and the ramp."""


def read(run):
    return run['setup_s']
