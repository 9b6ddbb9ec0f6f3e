"""Mean device time of the jitted decode step, from the profiler trace."""
from bench.metrics._window import STEP_MODULE

UNIT = "ms"


def read(rec):
    n = rec.trace["module_n"].get(STEP_MODULE, 0)
    if not n:
        return None
    return 1e3 * rec.trace["module_s"][STEP_MODULE] / n
