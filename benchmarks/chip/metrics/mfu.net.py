"""Conv FLOPs completed over the window as % of the chip's peak FLOP/s."""


def read(run):
    return run.step_mfu()
