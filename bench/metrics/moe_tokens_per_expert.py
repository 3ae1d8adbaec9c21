"""Tokens each active held expert computed, per expert layer and decode
tick, in the traced slice: the change of the program's counter
``serve_moe_tokens_total`` (live token -> held-expert assignments) over
that of ``serve_moe_experts_active_total`` (held experts that computed at
least one live token), both summed over the expert layers, between the
slice's two ends.  How near the expert layer's load comes to that of the
deployment the configuration stands for; nothing to read in a model
without experts."""

TOKENS = "serve_moe_tokens_total"
ACTIVE = "serve_moe_experts_active_total"


def read(rec):
    t = rec.get("trace")
    if not t:
        return None
    a, b = t["counters_start"], t["counters_stop"]
    active = b.get(ACTIVE, 0.0) - a.get(ACTIVE, 0.0)
    if active <= 0:
        return None
    return (b.get(TOKENS, 0.0) - a.get(TOKENS, 0.0)) / active
