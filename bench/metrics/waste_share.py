"""The paper's Resource Wastage over the second half of the window: the
program's processed tokens (prefill at padded length, decode on every
copy, snapshot overhead) less one clean copy of each request completed,
over the processed tokens (ServeMetrics counters).  Taking the second
half leaves work in flight at both ends, so it does not read as waste."""


def read(rec):
    a, b = rec["counters_mid"], rec["counters_end"]
    usage = b["usage_tokens"] - a["usage_tokens"]
    useful = b["useful_tokens"] - a["useful_tokens"]
    return 100.0 * (usage - useful) / usage if usage > 0 else None
