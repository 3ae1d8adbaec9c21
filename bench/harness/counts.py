"""Operations and bytes a decoder step needs, from the configuration's
shapes alone (``configs/<config>.json`` ``model``), never from the
program: the count is of the algorithm, so no implementation can push a
share computed from it past its peak.

Covers the dense decoder family: GQA attention with rotary positions, a
SwiGLU MLP, an optional RMSNorm scale, tied or untied output head.  A
configuration of another architecture brings its own counts in its
module (``Counts``; the contract is in ``spec.py``).
"""
from __future__ import annotations

BF16_BYTES = 2


class Counts:
    """The counts of one configuration: each of ``prefill_flops``,
    ``decode_flops`` and ``decode_least_bytes`` that its module
    (``configs/<config>.py``) defines, else the dense one of this file.
    A module's own ``decode_least_bytes`` also takes the change of the
    program's counters over the tick (``per_tick_counters``)."""

    def __init__(self, module):
        self.prefill_flops = getattr(module, "prefill_flops", prefill_flops)
        self.decode_flops = getattr(module, "decode_flops", decode_flops)
        self._least_bytes = getattr(module, "decode_least_bytes", None)
        self.per_tick_counters = self._least_bytes is not None

    def decode_least_bytes(self, m: dict, attended: list[int],
                           counters: dict | None) -> float:
        if self._least_bytes is None:
            return decode_least_bytes(m, attended)
        return self._least_bytes(m, attended, counters)


def window_flops(c: Counts, m: dict, ticks) -> float:
    """Forward FLOPs of the true prompt tokens prefilled and of every
    decode token computed over ``ticks``."""
    flops = 0.0
    for t in ticks:
        flops += sum(c.prefill_flops(m, p) for p, _ in t.prefills)
        flops += sum(c.decode_flops(m, a) for a in t.decoded)
    return flops


def slice_least_bytes(c: Counts, m: dict, ticks, counters=None) -> float:
    """Least HBM bytes of the decode ticks among ``ticks``; ``counters``
    holds the program counters' change over each tick where the
    configuration's count reads them."""
    counters = counters or [None] * len(ticks)
    return sum(c.decode_least_bytes(m, t.decoded, d)
               for t, d in zip(ticks, counters) if t.decoded)


def _dims(m: dict):
    d, h, kv = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // h
    return d, h, kv, hd, m["d_ff"], m["vocab_size"], m["n_layers"]


def layer_matmul_params(m: dict) -> int:
    d, h, kv, hd, ff, _, _ = _dims(m)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff


def _norm_params(m: dict) -> int:
    """Scale vectors: two per layer and a final one, for RMSNorm."""
    d, *_, n_layers = _dims(m)
    return (2 * n_layers + 1) * d if m.get("norm_type") == "rmsnorm" else 0


def forward_flops(m: dict, *, tokens: int, context: int,
                  logits_rows: int) -> float:
    """FLOPs of a forward pass over ``tokens`` query tokens whose attention
    spans ``context`` (key, query) pairs in all, with ``logits_rows`` rows
    of the output head computed."""
    d, h, _, hd, _, v, n_layers = _dims(m)
    matmul = 2.0 * tokens * n_layers * layer_matmul_params(m)
    attention = 4.0 * n_layers * h * hd * context   # q.k and p.v
    return matmul + attention + 2.0 * logits_rows * d * v


def prefill_flops(m: dict, prompt_len: int) -> float:
    """Causal prefill of the true prompt; one logits row (the last)."""
    p = int(prompt_len)
    return forward_flops(m, tokens=p, context=p * (p + 1) // 2,
                         logits_rows=1)


def decode_flops(m: dict, attended: int) -> float:
    """One decoded token attending ``attended`` positions (its own
    included)."""
    return forward_flops(m, tokens=1, context=int(attended), logits_rows=1)


def decode_least_bytes(m: dict, attended: list[int]) -> float:
    """Least HBM bytes of one batched decode tick over live slots that
    attend ``attended`` positions each: every weight once in bf16 (the
    layers, the norms, the output head, and the embedding rows of the
    batch's tokens), each slot's cached keys and values before this
    token, and each slot's one new key and value row."""
    d, _, kv, hd, _, v, n_layers = _dims(m)
    weights = (n_layers * layer_matmul_params(m) + _norm_params(m)
               + v * d + len(attended) * d)
    kv_row = n_layers * 2 * kv * hd               # keys + values, one token
    cached = sum(max(int(a) - 1, 0) for a in attended)
    return BF16_BYTES * (weights + kv_row * cached + kv_row * len(attended))
