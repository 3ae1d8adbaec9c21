"""Device time per execution of the batched decode program, from the
trace."""


def read(rec):
    t = rec.get("trace")
    p = t and t["programs"].get(rec["programs"]["decode"])
    return 1e3 * p["device_s"] / p["count"] if p and p["count"] else None
