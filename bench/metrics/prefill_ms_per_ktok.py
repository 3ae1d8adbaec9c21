"""Device time of the prefill programs per thousand padded prompt tokens
they ran in the traced slice."""


def read(rec):
    t = rec.get("trace")
    p = t and t["programs"].get(rec["programs"]["prefill"])
    if not p or not p["count"] or not t["prefill_padded_tokens"]:
        return None
    return 1e3 * p["device_s"] / (t["prefill_padded_tokens"] / 1e3)
