"""Share of the first chip's idle time in the traced window during which
a background task ran: the idle intervals overlapped by the union of the
program's ``sched_task`` spans, mapped onto the profiler's clock, over
all idle time. None when the tracer is off or the trace saw no chip."""
from bench.metrics._spans import length, overlap, traced

UNIT = "fraction"


def read(rec):
    sp = traced(rec)
    if sp is None:
        return None
    idle = length(sp["idle"])
    return length(overlap(sp["idle"], sp["background"])) / idle if idle else 0.0
