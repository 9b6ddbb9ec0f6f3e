"""Backend loads inside the pin's swap-ins (decode, host CRC and the
device checksum round trip), mean per pin in the window: the program's
``backend_load`` window total over the ``pin_step`` window count."""
from bench.metrics._spans import count, ms_per, window_stages

UNIT = "ms"


def read(rec):
    st = window_stages(rec)
    if st is None:
        return None
    return ms_per(st, "backend_load", count(st, "pin_step"))
