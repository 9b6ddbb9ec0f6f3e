"""Slot allocation inside the pin's swap-ins, synchronous reclaim
included, mean per pin in the window: the program's ``swap_in_alloc``
window total over the ``pin_step`` window count."""
from bench.metrics._spans import count, ms_per, window_stages

UNIT = "ms"


def read(rec):
    st = window_stages(rec)
    if st is None:
        return None
    return ms_per(st, "swap_in_alloc", count(st, "pin_step"))
