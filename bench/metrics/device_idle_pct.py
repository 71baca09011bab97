"""Device: share of the traced window in which no operation ran on the
chip (1 - union of the device operations' intervals / window)."""


def read(rec):
    t = rec.traced or {}
    if not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
