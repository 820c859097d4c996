"""Share of the traced window of the serving cell in which no operation
ran on the device."""

from chipbench import trace


def read(run):
    return 100.0 * (1.0 - trace.busy_s(run.trace) / run.trace.window_s)
