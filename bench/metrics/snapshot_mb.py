"""Megabytes (10^6 bytes) the program copied off the device per decode
snapshot in the traced slice: the change of its counter
``serve_bytes_total{kind=snapshot}`` over that of
``serve_events_total{kind=snapshot}`` between the slice's two ends."""

BYTES = "serve_bytes_total{kind=snapshot}"
SNAPSHOTS = "serve_events_total{kind=snapshot}"


def read(rec):
    t = rec.get("trace")
    if not t:
        return None
    a, b = t["counters_start"], t["counters_stop"]
    n = b.get(SNAPSHOTS, 0.0) - a.get(SNAPSHOTS, 0.0)
    if n <= 0:
        return None
    return (b.get(BYTES, 0.0) - a.get(BYTES, 0.0)) / n / 1e6
