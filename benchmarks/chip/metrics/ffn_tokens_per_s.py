"""Tokens (rows of the hidden state through all layers) completed over the whole window, per second."""


def read(run):
    return run.units_per_s
