"""Operations and bytes a decoder step needs, from the configuration's
shapes alone (``configs/<config>.json`` ``model``), never from the
program: the count is of the algorithm, so no implementation can push a
share computed from it past its peak.

Covers the dense decoder family the configurations use: GQA attention
with rotary positions, a SwiGLU MLP, an optional RMSNorm scale, tied or
untied output head.
"""
from __future__ import annotations

BF16_BYTES = 2


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
