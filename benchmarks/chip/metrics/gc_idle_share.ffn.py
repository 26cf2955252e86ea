"""% of the traced window, over the devices, in which the device idled while the host was inside a garbage-collector pause (``obs.gc``).

A program that does not span its collections declares no ``GC_SPAN`` in
``repro.obs.trace``: the metric is then absent, where a program that does
reads 0 for a window without a pause.
"""

import program_spans


def read(run):
    name = program_spans.gc_span()
    spans = program_spans.of_run(run)
    if name is None or spans is None:
        return None
    return 100.0 * spans.idle_inside_s(name, run.trace) \
        / len(run.trace.devices) / run.trace.window_s
