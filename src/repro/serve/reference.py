"""Static one-shot greedy reference decoder for token-parity checks.

Decodes each request independently — batch=1, exact-length prefill (no
bucket padding), scalar-position decode loop — through the same
``lm.prefill`` / ``lm.decode_step`` model code the engine jits, but via a
*different* batching path: no slot reuse, no padding, no per-slot position
vectors, no idle-row masking.  Token-for-token agreement between
:func:`greedy_reference` and :class:`~repro.serve.engine.ServeEngine` is
therefore evidence that the engine's continuous-batching machinery (bucket
padding + ``last_idx``, freed-slot reuse, masked cache commits, snapshot
restore) is output-transparent for every model family.

Exactness argument: masked attention scores are set to ``-1e30``, which
underflows to exactly ``0.0`` after the softmax ``exp`` — padded keys
contribute nothing, bit-for-bit, so bucketed and exact-length prefill agree
on every admitted position (and the recurrent families never see padding in
either path).  That holds in float32; in bfloat16 a change of shape (the
padded prompt, the batch of slots) may change the order XLA sums in, and a
last-bit difference then flips a near-tied greedy token.  For bf16 models
at published width, :func:`reference_logits` gives the same path's logits
at every step, fed the tokens the engine delivered, so the two can be
compared within a tolerance instead of token for token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.steps import make_prefill_step, make_serve_step
from repro.models.config import ModelConfig

__all__ = ["greedy_reference", "reference_logits"]


def _prefill_batch(cfg: ModelConfig, req) -> dict:
    batch = {"tokens": jnp.asarray(
        np.asarray(req.prompt, np.int32))[None]}
    if cfg.is_encdec:
        batch["frames"] = jnp.asarray(
            np.asarray(req.frames, np.float32))[None]
    if cfg.n_image_tokens:
        batch["image_embeds"] = jnp.asarray(
            np.asarray(req.image_embeds, np.float32))[None]
    return batch


def _static_decode(params, cfg: ModelConfig, requests, cache_len: int, *,
                   q_chunk: int, forced=None):
    """Yield ``(rid, tokens, logits)`` per request: batch=1 exact-length
    prefill, then the scalar-position decode loop.  Each step is fed the
    previous step's argmax, or ``forced[rid]``'s token where given;
    ``logits`` holds the (1, V) device array of every step."""
    serve = jax.jit(make_serve_step(cfg))
    prefills: dict[int, object] = {}
    offset = cfg.n_image_tokens or 0
    for req in requests:
        p = req.prompt_len
        fn = prefills.get(p)
        if fn is None:
            fn = jax.jit(make_prefill_step(cfg, cache_len,
                                           q_chunk=min(q_chunk, p)))
            prefills[p] = fn
        logits, cache = fn(params, _prefill_batch(cfg, req))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        tokens, rows = [int(np.asarray(tok)[0, 0])], [logits]
        feed = None if forced is None else forced[req.rid]
        for i in range(req.max_new_tokens - 1):
            if feed is not None:
                tok = jnp.asarray([[feed[i]]], jnp.int32)
            tok, logits, cache = serve(params, cache, tok,
                                       jnp.int32(offset + p + i))
            tokens.append(int(np.asarray(tok)[0, 0]))
            rows.append(logits)
        yield req.rid, tokens, rows


def greedy_reference(params, cfg: ModelConfig, requests, cache_len: int, *,
                     q_chunk: int = 64) -> dict[int, list[int]]:
    """Greedy tokens for each request, rid -> tokens, batch=1 static decode.

    ``cache_len`` should match the engine's so both paths attend over the
    same cache geometry (same rolling-window size for RG-LRU hybrids).
    """
    return {rid: tokens for rid, tokens, _ in _static_decode(
        params, cfg, requests, cache_len, q_chunk=q_chunk)}


def reference_logits(params, cfg: ModelConfig, requests, cache_len: int,
                     tokens: dict[int, list[int]], *,
                     q_chunk: int = 64) -> dict[int, np.ndarray]:
    """rid -> (max_new_tokens, V) float32 logits of the same static path,
    fed ``tokens[rid]`` instead of its own argmax: row ``k`` is what the
    reference computes for token ``k`` given tokens ``0..k-1``.  Where
    every ``tokens[rid][k]`` is row ``k``'s argmax, ``tokens`` is exactly
    :func:`greedy_reference`'s output."""
    return {rid: np.concatenate([np.asarray(r, np.float32) for r in rows])
            for rid, _, rows in _static_decode(
                params, cfg, requests, cache_len, q_chunk=q_chunk,
                forced=tokens)}
