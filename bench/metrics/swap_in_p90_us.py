"""p90 of the swap engine's per-MS swap-in time (Metrics.swap_in_latency,
the samples recorded in the window), in cells whose Taiji memory is
overcommitted."""
import numpy as np

UNIT = "us"


def read(rec):
    samples = rec.loop.system.metrics.swap_in_latency.samples[rec.loop.swapin_n0:]
    if not rec.loop.swaps or not samples:
        return None
    return float(np.percentile(np.asarray(samples, np.float64), 90)) / 1e3
