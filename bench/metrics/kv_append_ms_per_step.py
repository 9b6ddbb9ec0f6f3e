"""Host time of each window step's ElasticKVCache.append_kv calls (the
harness's span around them), mean per step."""
import numpy as np

UNIT = "ms"


def read(rec):
    steps = rec.loop.steps
    if not steps:
        return None
    return 1e3 * float(np.mean([s["append_s"] for s in steps]))
