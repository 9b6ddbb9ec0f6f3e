"""Time hv_sched's background tasks ran (LRU scans, reclaim; both
scheduler threads summed), over the window from the first step's
dispatch to the last step's readback: the program's ``sched_task``
window total over that length."""
from bench.metrics._spans import total_ms, window_stages

UNIT = "fraction"


def read(rec):
    st = window_stages(rec)
    if st is None:
        return None
    steps = rec.loop.steps
    span_s = steps[-1]["t_ready"] - steps[0]["t0"] if steps else 0.0
    return total_ms(st, "sched_task") / 1e3 / span_s if span_s > 0 else 0.0
