"""Share of the traced window in which the device sat idle while the
trainer read its next batch and copied it to the device: idle gaps whose
midpoint falls in an innermost ``train_step.data`` span.  A program
without the span reads nothing."""

from chipbench import trace

SPAN = "train_step.data"


def read(run):
    if not any(h.name == SPAN for h in run.trace.host):
        return None
    idle = dict(trace.attribute_gaps(run.trace)).get(SPAN, 0.0)
    return 100.0 * idle / run.trace.window_s
