"""Share of the HBM roofline the swap kernels reach: the bytes each call
needs (bench.counts.swap_kernel_bytes, from the shapes in the call's HLO
text) over peak bandwidth, over the summed device time of the calls."""
import re

from bench import counts
from bench.trace_reduce import stable_name

UNIT = "%"
U32_BLOCKS = re.compile(r"u32\[(\d+),(\d+),128\]")


def call_bytes(kernel: str, hlo: str) -> float:
    """Bytes one call needs, from its HLO text: the gathered output, the
    scattered blocks (the last operand), or the scanned input."""
    shapes = U32_BLOCKS.findall(hlo)
    n, rows = shapes[-1] if kernel == "scatter_blocks" else shapes[0]
    return counts.swap_kernel_bytes(kernel, int(n), int(rows))


def read(rec):
    if rec.peaks is None:
        return None
    need = busy = 0.0
    for e in rec.trace["ops"]:
        kernel = stable_name(e[2])
        if kernel in counts.SWAP_KERNELS:
            need += call_bytes(kernel, e[2])
            busy += e[4] / 1e9
    if not busy:
        return None
    return 100.0 * need / rec.peaks["hbm_bytes_per_s"] / busy
