"""% of the device's busy time spent outside the Pallas kernels: pads, relayouts, concatenations, residual adds."""


def read(run):
    return run.glue_share()
