"""Share of the traced slice in which no operation ran on the device:
1 - (union of the device's op intervals) / (slice length)."""


def read(rec):
    t = rec.get("trace")
    if not t or t["slice_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["slice_s"])
