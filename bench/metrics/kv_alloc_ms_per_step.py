"""Block allocation inside ElasticKVCache.append_kv (slot alloc,
synchronous reclaim, the frame's zero-fill), mean per window step: the
program's ``kv_alloc`` window total over the steps."""
from bench.metrics._spans import ms_per, window_stages

UNIT = "ms"


def read(rec):
    st = window_stages(rec)
    if st is None:
        return None
    return ms_per(st, "kv_alloc", len(rec.loop.steps))
