"""Host ms per image in the runner's pre-flight (``kernel.preflight`` self time), from the program's spans in the trace."""

import program_spans


def read(run):
    return program_spans.per_step_ms(run, ("kernel.preflight",))
