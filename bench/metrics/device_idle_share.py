"""Share of the traced window in which no operation ran on the device:
1 - union of device-op intervals / window (%)."""


def read(rec):
    w = rec.trace["window_s"]
    return 100.0 * (1.0 - rec.trace["busy_s"] / w) if w > 0 else None
