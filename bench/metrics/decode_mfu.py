"""Model FLOPs of every token the decode step processed in the traced
window (fed and generated), over the profiler's traced window times the
chip's bf16 peak: the whole step's share of the peak."""
from bench import counts
from bench.metrics._window import traced_steps

UNIT = "%"


def read(rec):
    steps = traced_steps(rec)
    if not steps or rec.peaks is None or not rec.trace["window_s"]:
        return None
    flops = sum(counts.decode_flops(rec.arch, s["kv_lens"], root=rec.cell.root)
                for s in steps)
    return 100.0 * flops / (rec.trace["window_s"] * rec.peaks["bf16_flops_per_s"])
