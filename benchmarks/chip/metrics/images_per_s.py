"""Images completed over the whole window, per second."""


def read(run):
    return run.units_per_s
