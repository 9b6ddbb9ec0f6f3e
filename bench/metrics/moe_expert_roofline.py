"""Share of the HBM roofline the held-expert kernel reaches: the bytes
its calls need -- each call one layer's held experts as far as the
step's rows reach them, and the rows routed to them, by the family's
``expert_call_bytes`` at the rows of the traced steps -- over peak
bandwidth, over the calls' summed device time. The calls are found by
name in the ops' HLO text."""
import numpy as np

from bench.cell import family_module
from bench.metrics._window import traced_steps
from bench.metrics.moe_expert_ms_per_step import KERNEL
from bench.trace_reduce import stable_name

UNIT = "%"


def read(rec):
    family = family_module(rec.arch.family, rec.cell.root)
    steps = traced_steps(rec)
    if rec.peaks is None or not steps or not hasattr(family, "expert_call_bytes"):
        return None
    busy = [e[4] / 1e9 for e in rec.trace["ops"] if stable_name(e[2]) == KERNEL]
    if not busy:
        return None
    per_call = np.mean([family.expert_call_bytes(rec.arch, len(s["kv_lens"]))
                        for s in steps])
    return 100.0 * len(busy) * per_call / rec.peaks["hbm_bytes_per_s"] / sum(busy)
