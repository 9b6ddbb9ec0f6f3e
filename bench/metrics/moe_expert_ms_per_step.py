"""Device time of the held-expert kernel (``expert_swiglu``, the decode
step's expert layer) in the traced window, per decode step traced."""
from bench.metrics._window import STEP_MODULE

UNIT = "ms"
KERNEL = "expert_swiglu"


def read(rec):
    busy = rec.trace["op_s"].get(KERNEL, 0.0)
    n = rec.trace["module_n"].get(STEP_MODULE, 0)
    if not busy or not n:
        return None
    return 1e3 * busy / n
