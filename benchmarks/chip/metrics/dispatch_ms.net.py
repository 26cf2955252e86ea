"""Host ms per image dispatching the convs and the eager glue (``network.conv`` and ``network.glue`` self time), from the program's spans in the trace."""

import program_spans


def read(run):
    return program_spans.per_step_ms(run, ("network.conv", "network.glue"))
