"""Host time of the DMA pin at admission, mean per pin in the window:
the program's ``pin_step`` span (each block's swap-in and the pin, not
the unpin), window total over window count."""
from bench.metrics._spans import count, ms_per, window_stages

UNIT = "ms"


def read(rec):
    st = window_stages(rec)
    if st is None:
        return None
    return ms_per(st, "pin_step", count(st, "pin_step"))
