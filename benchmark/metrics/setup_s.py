"""Process start to the first instant of the measured window: import,
weights, compile or cache load, warm-up and, for serving, the fill."""


def read(run):
    return run["setup_s"]
