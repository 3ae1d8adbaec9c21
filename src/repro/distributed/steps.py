"""Train / serve step builders (pjit-able, mesh-agnostic)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import lm
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, adamw_update, cosine_schedule


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig | None = None, *,
                    accum_steps: int = 1, q_chunk: int = 1024,
                    xent_chunk: int = 512, warmup: int = 100,
                    total_steps: int = 10_000, grad_shardings=None):
    """Returns ``train_step(params, opt_state, batch) -> (params, opt_state,
    metrics)``.  ``accum_steps > 1`` scans over microbatches (sequential
    gradient accumulation) so activation memory is bounded by one microbatch.
    ``grad_shardings`` (a NamedSharding tree mirroring params) constrains the
    accumulated-gradient buffer -- under ZeRO-1 this turns the per-microbatch
    gradient all-reduce into a reduce-scatter onto the optimizer shards.
    """
    opt_cfg = opt_cfg or AdamWConfig()

    def loss_fn(p, mb):
        loss, metrics = lm.forward_train(p, cfg, mb, q_chunk=q_chunk,
                                         xent_chunk=xent_chunk)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                return x.reshape(accum_steps, b // accum_steps, *x.shape[1:])

            mbs = jax.tree.map(split, batch)

            def acc(carry, mb):
                gsum, lsum = carry
                (l, _), g = grad_fn(params, mb)
                gsum = jax.tree.map(jnp.add, gsum, g)
                if grad_shardings is not None:
                    gsum = jax.lax.with_sharding_constraint(gsum,
                                                            grad_shardings)
                return (gsum, lsum + l), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if grad_shardings is not None:
                zeros = jax.lax.with_sharding_constraint(zeros,
                                                         grad_shardings)
            (grads, loss), _ = jax.lax.scan(acc, (zeros, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss = loss / accum_steps
            metrics = {}
        lr_scale = cosine_schedule(opt_state["step"], warmup=warmup,
                                   total=total_steps)
        params, opt_state, om = adamw_update(opt_cfg, params, grads,
                                             opt_state, lr_scale=lr_scale)
        out = {"loss": loss, **om}
        return params, opt_state, out

    return train_step


def make_serve_step(cfg: ModelConfig, *, cache_axes=None):
    """One greedy decode step: (params, cache, tokens (B,1), pos) ->
    (next_tokens (B,1), logits fp32, cache).  ``pos`` may be a scalar
    (static batch, all rows at the same position) or a (B,) vector
    (continuous batching, per-slot positions).

    With ``cache_axes`` (the per-leaf batch-axis pytree from
    ``repro.serve.snapshot.cache_batch_axes``) the returned step takes an
    extra ``live`` (B,) bool argument and only commits cache writes for live
    rows — freed slots keep their previous row bit-identical.  Without this,
    idle slots' stale ``last_token``/``pos`` would silently rewrite cache
    rows every tick: harmless for dense KV only because prefill overwrites
    the whole row on reuse, but fatal for recurrent (RWKV / RG-LRU) state,
    which accumulates.

    For an MoE model the masked step's middle output is ``(logits,
    moe_counts)``: ``moe_counts`` (2,) int32 holds, summed over the expert
    layers, the held experts that computed at least one live token and
    the live token -> held-expert assignments (``decode_moe_counts``)."""

    def serve_step(params, cache, tokens, pos):
        logits, cache = lm.decode_step(params, cfg, cache, tokens, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return nxt, logits, cache

    if cache_axes is None:
        return serve_step

    def serve_step_masked(params, cache, tokens, pos, live):
        logits, new_cache, routes = lm.decode_step(
            params, cfg, cache, tokens, pos, return_routes=True)

        def commit(new, old, axis):
            shape = [1] * new.ndim
            shape[axis] = new.shape[axis]
            return jnp.where(live.reshape(shape), new, old)

        new_cache = jax.tree.map(commit, new_cache, cache, cache_axes)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        if routes is None:
            return nxt, logits, new_cache
        return nxt, (logits, decode_moe_counts(routes, live)), new_cache

    return serve_step_masked


def decode_moe_counts(routes, live):
    """``routes`` (L_moe, B, E_held) bool, ``live`` (B,) bool -> (2,)
    int32: held experts that computed at least one live token, and live
    token -> held-expert assignments, each summed over the layers."""
    hit = routes & live[None, :, None]
    return jnp.stack([jnp.sum(jnp.any(hit, axis=1)),
                      jnp.sum(hit)]).astype(jnp.int32)


def make_prefill_step(cfg: ModelConfig, cache_len: int, *,
                      q_chunk: int = 1024, with_last_idx: bool = False):
    """``with_last_idx=True`` returns ``prefill_step(params, batch,
    last_idx)`` where ``last_idx`` (B,) picks each row's true last prompt
    position (bucket-padded prompts, see ``lm.prefill``)."""
    if with_last_idx:
        def prefill_last_idx_step(params, batch, last_idx):
            return lm.prefill(params, cfg, batch, cache_len, q_chunk=q_chunk,
                              last_idx=last_idx)

        return prefill_last_idx_step

    def prefill_step(params, batch):
        return lm.prefill(params, cfg, batch, cache_len, q_chunk=q_chunk)

    return prefill_step
