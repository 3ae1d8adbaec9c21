"""Share of the HBM roofline reached by the batched decode program: the
least bytes its ticks in the traced slice needed (the configuration's
counts; harness/counts.py's dense ones: weights once in bf16, each live
slot's keys and values, one new row per slot) over its device time in
the trace times the peak bandwidth."""


def read(rec):
    t = rec.get("trace")
    if not t:
        return None
    p = t["programs"].get(rec["programs"]["decode"])
    if not p or not p["device_s"] or not t["decode_least_bytes"]:
        return None
    return 100.0 * t["decode_least_bytes"] / (
        p["device_s"] * rec["peaks"]["hbm_bytes_s"])
