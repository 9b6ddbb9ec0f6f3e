"""Host time in ElasticKVCache.prepare_step (swap-in and pin), mean per
request admitted in the window."""
import numpy as np

UNIT = "ms"


def read(rec):
    if not rec.loop.admit_s:
        return None
    return 1e3 * float(np.mean(rec.loop.admit_s))
