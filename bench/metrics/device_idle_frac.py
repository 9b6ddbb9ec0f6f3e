"""1 - (union of device op intervals) / (traced window)."""
UNIT = "fraction"


def read(rec):
    if not rec.trace["chips"]:
        return None
    return 1.0 - rec.trace["busy_s"] / rec.trace["window_s"]
