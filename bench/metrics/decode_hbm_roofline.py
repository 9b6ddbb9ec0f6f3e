"""Share of the HBM roofline the decode step reaches: the bytes it needs
(bench.counts.decode_bytes: weights, the K/V of each row's real length,
the K/V written) over peak bandwidth, over its device time."""
import numpy as np

from bench import counts
from bench.metrics._window import STEP_MODULE, traced_steps

UNIT = "%"


def read(rec):
    n = rec.trace["module_n"].get(STEP_MODULE, 0)
    steps = traced_steps(rec)
    if not n or not steps or rec.peaks is None:
        return None
    per_step = np.mean([counts.decode_bytes(rec.arch, s["kv_lens"], root=rec.cell.root)
                        for s in steps])
    need_s = n * per_step / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / rec.trace["module_s"][STEP_MODULE]
