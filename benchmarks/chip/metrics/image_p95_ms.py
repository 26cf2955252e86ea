"""95th percentile of the per-image latency over every image of the window."""


def read(run):
    return run.latency_quantile_ms(95)
