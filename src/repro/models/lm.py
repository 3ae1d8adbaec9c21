"""Model assembly for all assigned architectures.

Pure-functional API:

  init_params(key, cfg)                          -> params pytree
  serving_params(params, cfg)                    -> compute-dtype weights
  forward_train(params, cfg, batch)              -> (loss, metrics)
  prefill(params, cfg, batch, cache_len)         -> (last_logits, cache)
  init_cache(cfg, batch_size, cache_len)         -> cache pytree
  decode_step(params, cfg, cache, tokens, pos)   -> (logits, cache)

Layers are *stacked* along a leading L axis and traversed with ``lax.scan``
(+ optional ``jax.checkpoint``), keeping HLO size O(1) in depth -- essential
for the 512-device dry-run compiles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from . import rglru as rg
from . import rwkv6 as rw
from .config import ModelConfig
from .layers import (apply_norm, attention_decode, attention_forward,
                     dense_init, init_attention, init_mlp, init_moe,
                     init_norm, mlp_forward, moe_forward, _split)

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _stack_init(key, n, init_fn):
    """Initialize n layers and stack each leaf along axis 0."""
    keys = jax.random.split(key, n)
    layers = [init_fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def _compute_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def chunked_xent(h, w_out, targets, mask, *, chunk: int = 512):
    """Cross-entropy without materializing full (B, S, V) logits.

    The chunk body is rematerialized: without ``jax.checkpoint`` the scan
    saves every chunk's (B, C, V) fp32 logits for the backward pass, which
    costs ~seq/chunk x the live set (measured +50 GiB/device on the olmo /
    whisper train_4k dry-runs; see EXPERIMENTS.md section Perf, iteration 1).
    """
    b, s, d = h.shape
    chunk = min(chunk, s)
    n = s // chunk
    rem = s - n * chunk

    @jax.checkpoint
    def piece(hc, tc, mc):
        logits = (hc @ w_out).astype(jnp.float32)           # (B, C, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], -1)[..., 0]
        return jnp.sum((lse - gold) * mc), jnp.sum(mc)

    if n > 0:
        hcs = h[:, :n * chunk].reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
        tcs = targets[:, :n * chunk].reshape(b, n, chunk).transpose(1, 0, 2)
        mcs = mask[:, :n * chunk].reshape(b, n, chunk).transpose(1, 0, 2)

        def body(carry, inp):
            tot, cnt = carry
            t, c = piece(*inp)
            return (tot + t, cnt + c), None

        (tot, cnt), _ = jax.lax.scan(body, (0.0, 0.0), (hcs, tcs, mcs))
    else:
        tot = cnt = 0.0
    if rem:
        t, c = piece(h[:, n * chunk:], targets[:, n * chunk:],
                     mask[:, n * chunk:])
        tot, cnt = tot + t, cnt + c
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _init_dense_layer(key, cfg: ModelConfig, moe: bool):
    ks = _split(key, 4)
    p = {
        "ln1": init_norm(cfg, cfg.d_model),
        "attn": init_attention(ks[0], cfg),
    }
    if cfg.block_type != "parallel":
        p["ln2"] = init_norm(cfg, cfg.d_model)
    p["moe" if moe else "mlp"] = (
        init_moe(ks[1], cfg) if moe else init_mlp(ks[1], cfg))
    return p


def _init_rec_layer(key, cfg: ModelConfig):
    ks = _split(key, 2)
    return {
        "ln1": init_norm(cfg, cfg.d_model),
        "rec": rg.init_rglru_block(ks[0], cfg),
        "ln2": init_norm(cfg, cfg.d_model),
        "mlp": init_mlp(ks[1], cfg),
    }


def _init_attn_layer(key, cfg: ModelConfig):
    ks = _split(key, 2)
    return {
        "ln1": init_norm(cfg, cfg.d_model),
        "attn": init_attention(ks[0], cfg),
        "ln2": init_norm(cfg, cfg.d_model),
        "mlp": init_mlp(ks[1], cfg),
    }


def _init_rwkv_layer(key, cfg: ModelConfig):
    ks = _split(key, 2)
    return {
        "ln1": init_norm(cfg, cfg.d_model),
        "tm": rw.init_time_mix(ks[0], cfg),
        "ln2": init_norm(cfg, cfg.d_model),
        "cm": rw.init_channel_mix(ks[1], cfg),
    }


def _init_cross_layer(key, cfg: ModelConfig):
    ks = _split(key, 3)
    return {
        "ln1": init_norm(cfg, cfg.d_model),
        "attn": init_attention(ks[0], cfg),
        "ln_x": init_norm(cfg, cfg.d_model),
        "xattn": init_attention(ks[1], cfg),
        "ln2": init_norm(cfg, cfg.d_model),
        "mlp": init_mlp(ks[2], cfg),
    }


def hybrid_layout(cfg: ModelConfig) -> tuple[int, int]:
    """(#super blocks of [rec]*k+[attn], #tail rec layers)."""
    span = cfg.rec_per_attn + 1
    return cfg.n_layers // span, cfg.n_layers % span


def init_params(key, cfg: ModelConfig):
    ks = _split(key, 8)
    d = cfg.d_model
    params = {
        "embed": dense_init(ks[0], (cfg.vocab_size, d), in_axis=1),
        "final_norm": init_norm(cfg, d),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], (d, cfg.vocab_size))

    if cfg.rwkv:
        params["ln_in"] = init_norm(cfg, d)
        params["layers"] = _stack_init(
            ks[2], cfg.n_layers, lambda k: _init_rwkv_layer(k, cfg))
    elif cfg.rglru:
        n_super, n_tail = hybrid_layout(cfg)
        params["super"] = _stack_init(ks[2], n_super, lambda k: {
            "rec": _stack_init(k, cfg.rec_per_attn,
                               lambda k2: _init_rec_layer(k2, cfg)),
            "attn": _init_attn_layer(jax.random.fold_in(k, 1), cfg),
        })
        if n_tail:
            params["tail"] = _stack_init(
                ks[3], n_tail, lambda k: _init_rec_layer(k, cfg))
    elif cfg.is_encdec:
        params["enc_pos"] = 0.02 * dense_init(ks[4], (cfg.n_frames, d))
        params["dec_pos"] = 0.02 * dense_init(ks[5], (cfg.max_decode_len, d))
        params["enc_layers"] = _stack_init(
            ks[2], cfg.encoder_layers, lambda k: _init_attn_layer(k, cfg))
        params["enc_norm"] = init_norm(cfg, d)
        params["layers"] = _stack_init(
            ks[3], cfg.n_layers, lambda k: _init_cross_layer(k, cfg))
    else:
        # leading dense layers (DeepSeek's first_k_dense_replace) stack
        # apart from the rest, which hold the experts of an MoE model
        if cfg.n_dense_layers:
            params["dense_layers"] = _stack_init(
                ks[6], cfg.n_dense_layers,
                lambda k: _init_dense_layer(k, cfg, moe=False))
        params["layers"] = _stack_init(
            ks[2], cfg.n_layers - cfg.n_dense_layers,
            lambda k: _init_dense_layer(k, cfg, moe=cfg.is_moe))
    pdt = jnp.dtype(cfg.param_dtype)
    if pdt != jnp.float32:
        # production dtype: bf16 weights on device; the fp32 master copy
        # lives (sharded) in the optimizer state (ZeRO-1)
        params = jax.tree.map(lambda x: x.astype(pdt), params)
    return params


# Leaves the model reads in float32: the norms' gains and biases (MLA's
# ``kv_norm`` too), RWKV-6's decay base ``ww``, bonus ``u`` and head
# group-norm gain ``ln_scale``, and RG-LRU's gate biases ``ba``/``bx`` and
# ``lam``.  It reads every other leaf only as ``.astype(compute_dtype)``.
FLOAT32_LEAVES = frozenset({"scale", "bias", "ww", "u", "ln_scale", "ba",
                            "bx", "lam"})


def serving_params(params, cfg: ModelConfig):
    """The weights as the model reads them: each leaf read only as
    ``.astype(compute_dtype)`` cast to the compute dtype, the leaves of
    ``FLOAT32_LEAVES`` left as they are.  Every output is the same bit for
    bit, since each matmul already ran on these values; the programs just
    stop casting the weights on every call.  A leaf already in the compute
    dtype is returned as it is."""
    dtype = _compute_dtype(cfg)

    def cast(path, x):
        if path[-1].key in FLOAT32_LEAVES or x.dtype == dtype:
            return x
        return x.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, params)


# ---------------------------------------------------------------------------
# blocks (single-layer forward, used under scan)
# ---------------------------------------------------------------------------

def _ffn(p, h, cfg: ModelConfig):
    """The layer's MLP or expert layer: (out, aux_loss, routes or None)."""
    if "moe" in p:
        return moe_forward(p["moe"], h, cfg)
    return mlp_forward(p["mlp"], h), 0.0, None


def _dense_block(p, x, cfg: ModelConfig, positions, *, mode="causal",
                 window=0, q_chunk=1024):
    if cfg.block_type == "parallel":                  # Cohere command-r
        h = apply_norm(cfg, p["ln1"], x)
        a = attention_forward(p["attn"], h, cfg, positions=positions,
                              mode=mode, window=window, q_chunk=q_chunk)
        m, aux, _ = _ffn(p, h, cfg)
        return x + a + m, aux
    h = apply_norm(cfg, p["ln1"], x)
    x = x + attention_forward(p["attn"], h, cfg, positions=positions,
                              mode=mode, window=window, q_chunk=q_chunk)
    m, aux, _ = _ffn(p, apply_norm(cfg, p["ln2"], x), cfg)
    return x + m, aux


def _rec_block(p, x, cfg: ModelConfig):
    h = apply_norm(cfg, p["ln1"], x)
    r, _ = rg.rglru_block_forward(p["rec"], h, cfg)
    x = x + r
    x = x + mlp_forward(p["mlp"], apply_norm(cfg, p["ln2"], x))
    return x


def _attn_block(p, x, cfg: ModelConfig, positions, *, mode, window, q_chunk):
    h = apply_norm(cfg, p["ln1"], x)
    x = x + attention_forward(p["attn"], h, cfg, positions=positions,
                              mode=mode, window=window, q_chunk=q_chunk)
    x = x + mlp_forward(p["mlp"], apply_norm(cfg, p["ln2"], x))
    return x


def _rwkv_block(p, x, cfg: ModelConfig):
    h = apply_norm(cfg, p["ln1"], x)
    zeros = jnp.zeros_like(x[:, 0])
    t, _ = rw.time_mix_forward(p["tm"], h, zeros, cfg)
    x = x + t
    h = apply_norm(cfg, p["ln2"], x)
    c, _ = rw.channel_mix_forward(p["cm"], h, zeros)
    return x + c


def _cross_block(p, x, cfg: ModelConfig, positions, enc_out, q_chunk):
    h = apply_norm(cfg, p["ln1"], x)
    x = x + attention_forward(p["attn"], h, cfg, positions=positions,
                              mode="causal", q_chunk=q_chunk)
    h = apply_norm(cfg, p["ln_x"], x)
    x = x + attention_forward(p["xattn"], h, cfg, positions=positions,
                              mode="cross", context=enc_out, q_chunk=q_chunk)
    x = x + mlp_forward(p["mlp"], apply_norm(cfg, p["ln2"], x))
    return x


# ---------------------------------------------------------------------------
# full forward (training)
# ---------------------------------------------------------------------------

def _scan_layers(layers, x, body, cfg: ModelConfig):
    fn = jax.checkpoint(body) if cfg.remat else body

    def step(carry, layer_p):
        x, aux = carry
        # sequence-parallel residual: the remat boundary tensor is sharded
        # over the model axis, cutting stored-activation HBM by its extent
        x = constrain(x, ("batch", "seq_resid", "embed"))
        x, a = fn(layer_p, x)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(step, (x, 0.0), layers)
    return x, aux


def _embed(params, cfg, tokens, dtype):
    x = params["embed"].astype(dtype)[tokens]
    return constrain(x, ("batch", "seq", "embed"))


def _encoder(params, cfg: ModelConfig, frames, q_chunk):
    dtype = _compute_dtype(cfg)
    x = frames.astype(dtype) + params["enc_pos"].astype(dtype)[None]
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1]), frames.shape[:2])

    def body(p, x):
        return _attn_block(p, x, cfg, pos, mode="bidir", window=0,
                           q_chunk=q_chunk), 0.0

    x, _ = _scan_layers(params["enc_layers"], x, body, cfg)
    return apply_norm(cfg, params["enc_norm"], x)


def backbone(params, cfg: ModelConfig, x, positions, *, enc_out=None,
             q_chunk: int = 1024):
    """Shared trunk: stacked blocks on embedded input x (B, S, D)."""
    aux = 0.0
    if cfg.rwkv:
        x = apply_norm(cfg, params["ln_in"], x)
        x, aux = _scan_layers(params["layers"], x,
                              lambda p, h: (_rwkv_block(p, h, cfg), 0.0), cfg)
    elif cfg.rglru:
        def super_body(p, h):
            def rec_step(hh, rp):
                return _rec_block(rp, hh, cfg), None
            h, _ = jax.lax.scan(rec_step, h, p["rec"])
            h = _attn_block(p["attn"], h, cfg, positions, mode="local",
                            window=cfg.window, q_chunk=q_chunk)
            return h, 0.0

        x, _ = _scan_layers(params["super"], x, super_body, cfg)
        if "tail" in params:
            def tail_body(p, h):
                return _rec_block(p, h, cfg), 0.0
            x, _ = _scan_layers(params["tail"], x, tail_body, cfg)
    elif cfg.is_encdec:
        def body(p, h):
            return _cross_block(p, h, cfg, positions, enc_out, q_chunk), 0.0
        x, _ = _scan_layers(params["layers"], x, body, cfg)
    else:
        def body(p, h):
            return _dense_block(p, h, cfg, positions, q_chunk=q_chunk)
        for name, _, _ in layer_stacks(cfg):
            x, a = _scan_layers(params[name], x, body, cfg)
            aux = aux + a
    return apply_norm(cfg, params["final_norm"], x), aux


def layer_stacks(cfg: ModelConfig) -> list[tuple[str, str, int]]:
    """The decoder's layer stacks in order: (params key, prefix of its
    cache leaves, depth).  The leading dense layers, where the model has
    them, keep their cache in leaves of their own (``dense_k``, ...), so
    that neither stack's scan slices or concatenates the other's cache."""
    dense = ([("dense_layers", "dense_", cfg.n_dense_layers)]
             if cfg.n_dense_layers else [])
    return dense + [("layers", "", cfg.n_layers - cfg.n_dense_layers)]


def output_weights(params, cfg: ModelConfig, dtype):
    if cfg.tie_embeddings:
        return params["embed"].astype(dtype).T
    return params["lm_head"].astype(dtype)


def forward_train(params, cfg: ModelConfig, batch, *, q_chunk: int = 1024,
                  xent_chunk: int = 512):
    """batch: {"tokens": (B,S) int32, "targets": (B,S) int32,
    "loss_mask": (B,S), ["frames"|"image_embeds"]: (B,T,D)}."""
    dtype = _compute_dtype(cfg)
    tokens = batch["tokens"]
    x = _embed(params, cfg, tokens, dtype)
    enc_out = None
    if cfg.is_encdec:
        enc_out = _encoder(params, cfg, batch["frames"], q_chunk)
        x = x + params["dec_pos"].astype(dtype)[None, :x.shape[1]]
    if cfg.n_image_tokens:
        img = batch["image_embeds"].astype(dtype)
        img = constrain(img, ("batch", "seq", "embed"))
        x = jnp.concatenate([img, x], axis=1)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    h, aux = backbone(params, cfg, x, positions, enc_out=enc_out,
                      q_chunk=q_chunk)
    if cfg.n_image_tokens:
        h = h[:, cfg.n_image_tokens:]
    w_out = output_weights(params, cfg, dtype)
    loss = chunked_xent(h, w_out, batch["targets"], batch["loss_mask"],
                        chunk=xent_chunk)
    total = loss + 0.01 * aux
    return total, {"xent": loss, "aux": aux}


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def _kv_shape(cfg, b, s):
    return (b, s, cfg.n_kv_heads, cfg.head_dim)


def _attn_cache_shapes(cfg, b, s) -> dict:
    """One layer's self-attention cache leaves: keys and values, or MLA's
    normalised latent and roped key."""
    if cfg.attention == "mla":
        return {"c_kv": (b, s, cfg.kv_lora_rank),
                "k_pe": (b, s, cfg.qk_rope_head_dim)}
    return {"k": _kv_shape(cfg, b, s), "v": _kv_shape(cfg, b, s)}


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None):
    """Zero cache covering positions [0, cache_len), in the compute dtype
    unless ``dtype`` says otherwise."""
    dtype = _compute_dtype(cfg) if dtype is None else dtype
    if cfg.rwkv:
        h = rw.n_heads(cfg)
        L = cfg.n_layers
        return {
            "S": jnp.zeros((L, batch, h, rw.HEAD_N, rw.HEAD_N), jnp.float32),
            "x_tm": jnp.zeros((L, batch, cfg.d_model), dtype),
            "x_cm": jnp.zeros((L, batch, cfg.d_model), dtype),
        }
    if cfg.rglru:
        n_super, n_tail = hybrid_layout(cfg)
        w = min(cfg.window, cache_len)
        cache = {
            "h": jnp.zeros((n_super, cfg.rec_per_attn, batch, cfg.lru_width),
                           jnp.float32),
            "conv": jnp.zeros((n_super, cfg.rec_per_attn, batch,
                               cfg.conv_width - 1, cfg.lru_width), dtype),
            "k": jnp.zeros((n_super, *_kv_shape(cfg, batch, w)), dtype),
            "v": jnp.zeros((n_super, *_kv_shape(cfg, batch, w)), dtype),
        }
        if n_tail:
            cache["tail_h"] = jnp.zeros((n_tail, batch, cfg.lru_width),
                                        jnp.float32)
            cache["tail_conv"] = jnp.zeros(
                (n_tail, batch, cfg.conv_width - 1, cfg.lru_width), dtype)
        return cache
    L = cfg.n_layers
    if cfg.is_encdec:
        cache = {name: jnp.zeros((L, *shape), dtype) for name, shape in
                 _attn_cache_shapes(cfg, batch, cache_len).items()}
        cache["cross_k"] = jnp.zeros((L, *_kv_shape(cfg, batch, cfg.n_frames)),
                                     dtype)
        cache["cross_v"] = jnp.zeros((L, *_kv_shape(cfg, batch, cfg.n_frames)),
                                     dtype)
        return cache
    return {prefix + name: jnp.zeros((depth, *shape), dtype)
            for _, prefix, depth in layer_stacks(cfg)
            for name, shape in _attn_cache_shapes(cfg, batch,
                                                  cache_len).items()}


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, *,
                return_routes: bool = False):
    """tokens: (B, 1) int32; pos: absolute position, scalar int32 or a
    per-row (B,) int32 vector (continuous batching: each batch slot decodes
    at its own position).  Returns (logits (B, V) fp32, new_cache); with
    ``return_routes`` (dense and MoE decoders) also the expert layers'
    token -> held-expert assignments, (L_moe, B, n_experts) bool, or None
    for a model without experts."""
    dtype = _compute_dtype(cfg)
    x = _embed(params, cfg, tokens, dtype)
    routes = None
    if cfg.is_encdec:
        if jnp.ndim(pos) > 0:
            x = x + jnp.take(params["dec_pos"].astype(dtype),
                             jnp.reshape(pos, (-1,)), axis=0)[:, None]
        else:
            x = x + jax.lax.dynamic_slice_in_dim(
                params["dec_pos"].astype(dtype), pos, 1, 0)[None]

    if cfg.rwkv:
        def step(x, inp):
            p, S, x_tm, x_cm = inp
            h = apply_norm(cfg, p["ln1"], x)
            t, (S2, x_tm2) = rw.time_mix_decode(p["tm"], h, (S, x_tm), cfg)
            x = x + t
            h = apply_norm(cfg, p["ln2"], x)
            c, x_cm2 = rw.channel_mix_decode(p["cm"], h, x_cm)
            return x + c, (S2, x_tm2.astype(x_tm.dtype),
                           x_cm2.astype(x_cm.dtype))

        x0 = apply_norm(cfg, params["ln_in"], x)
        x_out, (S_new, xtm_new, xcm_new) = jax.lax.scan(
            step, x0, (params["layers"], cache["S"], cache["x_tm"],
                       cache["x_cm"]))
        new_cache = {"S": S_new, "x_tm": xtm_new, "x_cm": xcm_new}
        h = apply_norm(cfg, params["final_norm"], x_out)
    elif cfg.rglru:
        def super_step(x, inp):
            p, hs, convs, k, v = inp

            def rec_step(x, rin):
                rp, h0, c0 = rin
                hh = apply_norm(cfg, rp["ln1"], x)
                r, st = rg.rglru_block_decode(rp["rec"], hh,
                                              {"h": h0, "conv": c0}, cfg)
                x = x + r
                x = x + mlp_forward(rp["mlp"],
                                    apply_norm(cfg, rp["ln2"], x))
                return x, (st["h"], st["conv"])

            x, (h_new, c_new) = jax.lax.scan(rec_step, x,
                                             (p["rec"], hs, convs))
            ap = p["attn"]
            hh = apply_norm(cfg, ap["ln1"], x)
            a, kv_new = attention_decode(ap["attn"], hh, {"k": k, "v": v},
                                         cfg, pos=pos, window=cfg.window)
            x = x + a
            x = x + mlp_forward(ap["mlp"], apply_norm(cfg, ap["ln2"], x))
            return x, (h_new, c_new, kv_new["k"], kv_new["v"])

        x, (h_new, c_new, k_new, v_new) = jax.lax.scan(
            super_step, x, (params["super"], cache["h"], cache["conv"],
                            cache["k"], cache["v"]))
        new_cache = dict(cache, h=h_new, conv=c_new, k=k_new, v=v_new)
        if "tail" in params:
            def tail_step(x, inp):
                rp, h0, c0 = inp
                hh = apply_norm(cfg, rp["ln1"], x)
                r, st = rg.rglru_block_decode(rp["rec"], hh,
                                              {"h": h0, "conv": c0}, cfg)
                x = x + r
                x = x + mlp_forward(rp["mlp"], apply_norm(cfg, rp["ln2"], x))
                return x, (st["h"], st["conv"])

            x, (th, tc) = jax.lax.scan(tail_step, x,
                                       (params["tail"], cache["tail_h"],
                                        cache["tail_conv"]))
            new_cache.update(tail_h=th, tail_conv=tc)
        h = apply_norm(cfg, params["final_norm"], x)
    elif cfg.is_encdec:
        def step(x, inp):
            p, k, v, xk, xv = inp
            hh = apply_norm(cfg, p["ln1"], x)
            a, kv_new = attention_decode(p["attn"], hh, {"k": k, "v": v},
                                         cfg, pos=pos)
            x = x + a
            hh = apply_norm(cfg, p["ln_x"], x)
            ax, _ = attention_decode(p["xattn"], hh, None, cfg, pos=pos,
                                     cross_kv=(xk, xv))
            x = x + ax
            x = x + mlp_forward(p["mlp"], apply_norm(cfg, p["ln2"], x))
            return x, (kv_new["k"], kv_new["v"])

        x, (k_new, v_new) = jax.lax.scan(
            step, x, (params["layers"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]))
        new_cache = dict(cache, k=k_new, v=v_new)
        h = apply_norm(cfg, params["final_norm"], x)
    else:
        def step(x, inp):
            p, c = inp
            hh = apply_norm(cfg, p["ln1"], x)
            a, c_new = attention_decode(p["attn"], hh, c, cfg, pos=pos)
            if cfg.block_type == "parallel":
                m, _, routes = _ffn(p, hh, cfg)
                x = x + a + m
            else:
                x = x + a
                m, _, routes = _ffn(p, apply_norm(cfg, p["ln2"], x), cfg)
                x = x + m
            return x, (c_new, routes)

        new_cache = dict(cache)
        for name, prefix, _ in layer_stacks(cfg):
            c = {n: cache[prefix + n] for n in _attn_cache_shapes(cfg, 1, 1)}
            x, (c, routes) = jax.lax.scan(step, x, (params[name], c))
            new_cache.update({prefix + n: v for n, v in c.items()})
        # the last stack's (L_moe, B, n_experts): token -> held-expert
        # assignments computed
        if routes is not None:
            routes = routes.reshape(*routes.shape[:2], -1)
        h = apply_norm(cfg, params["final_norm"], x)

    w_out = output_weights(params, cfg, dtype)
    logits = (h[:, 0] @ w_out).astype(jnp.float32)
    logits = constrain(logits, ("batch", "vocab"))
    if return_routes:
        return logits, new_cache, routes
    return logits, new_cache


# ---------------------------------------------------------------------------
# prefill: run the prompt through the trunk and build the decode cache
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, batch, cache_len: int, *,
            q_chunk: int = 1024, last_idx=None):
    """batch: {"tokens": (B, S)} (+ "frames" for enc-dec).  Returns
    (last-token logits (B, V) fp32, cache primed for position S).

    ``last_idx`` (optional (B,) int32) selects a per-row logits position
    instead of ``S - 1`` — used by the continuous-batching engine, which
    right-pads prompts up to a bucket length and needs the logits of each
    row's *true* last prompt token.  (Causality guarantees right padding
    cannot influence positions ``<= last_idx``; the decode loop overwrites
    each padded KV entry at position ``p`` before the mask first admits it.)
    """
    dtype = _compute_dtype(cfg)
    tokens = batch["tokens"]
    b = tokens.shape[0]
    x = _embed(params, cfg, tokens, dtype)
    if cfg.n_image_tokens:
        img = batch["image_embeds"].astype(dtype)
        x = jnp.concatenate([constrain(img, ("batch", "seq", "embed")), x], 1)
    s = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    cache = init_cache(cfg, b, cache_len, dtype=dtype)

    if cfg.rwkv:
        x = apply_norm(cfg, params["ln_in"], x)

        def step(x, p):
            h = apply_norm(cfg, p["ln1"], x)
            zeros = jnp.zeros_like(x[:, 0])
            t, (S_fin, x_tm) = rw.time_mix_forward(p["tm"], h, zeros, cfg)
            x = x + t
            h = apply_norm(cfg, p["ln2"], x)
            c, x_cm = rw.channel_mix_forward(p["cm"], h, zeros)
            return x + c, (S_fin, x_tm.astype(dtype), x_cm.astype(dtype))

        fn = jax.checkpoint(step) if cfg.remat else step
        x, (S_new, xtm, xcm) = jax.lax.scan(fn, x, params["layers"])
        cache = {"S": S_new, "x_tm": xtm, "x_cm": xcm}
        h = apply_norm(cfg, params["final_norm"], x)
    elif cfg.rglru:
        w = min(cfg.window, cache_len)
        slots = jnp.arange(s - w, s) % w if s >= w else jnp.arange(s)

        def rec_run(rp, x):
            h = apply_norm(cfg, rp["ln1"], x)
            r, st = rg.rglru_block_forward(rp["rec"], h, cfg,
                                           return_state=True)
            x = x + r
            x = x + mlp_forward(rp["mlp"], apply_norm(cfg, rp["ln2"], x))
            return x, st

        def super_step(x, p):
            def rec_step(xx, rp):
                return rec_run(rp, xx)
            x, sts = jax.lax.scan(rec_step, x, p["rec"])
            ap = p["attn"]
            hh = apply_norm(cfg, ap["ln1"], x)
            a, kv = attention_forward(ap["attn"], hh, cfg,
                                      positions=positions, mode="local",
                                      window=cfg.window, q_chunk=q_chunk,
                                      return_kv=True)
            x = x + a
            x = x + mlp_forward(ap["mlp"], apply_norm(cfg, ap["ln2"], x))
            k_c = jnp.zeros(_kv_shape(cfg, b, w), dtype).at[:, slots].set(
                kv[0][:, -w:].astype(dtype) if s >= w else kv[0].astype(dtype))
            v_c = jnp.zeros(_kv_shape(cfg, b, w), dtype).at[:, slots].set(
                kv[1][:, -w:].astype(dtype) if s >= w else kv[1].astype(dtype))
            return x, (sts["h"], sts["conv"], k_c, v_c)

        fn = jax.checkpoint(super_step) if cfg.remat else super_step
        x, (hs, convs, ks, vs) = jax.lax.scan(fn, x, params["super"])
        cache.update(h=hs, conv=convs, k=ks, v=vs)
        if "tail" in params:
            def tail_step(x, rp):
                return rec_run(rp, x)
            fn = jax.checkpoint(tail_step) if cfg.remat else tail_step
            x, sts = jax.lax.scan(fn, x, params["tail"])
            cache.update(tail_h=sts["h"], tail_conv=sts["conv"])
        h = apply_norm(cfg, params["final_norm"], x)
    else:
        enc_out = None
        if cfg.is_encdec:
            enc_out = _encoder(params, cfg, batch["frames"], q_chunk)
            x = x + params["dec_pos"].astype(dtype)[None, :s]

        def dense_step(x, p):
            hh = apply_norm(cfg, p["ln1"], x)
            a, kv = attention_forward(p["attn"], hh, cfg,
                                      positions=positions, mode="causal",
                                      q_chunk=q_chunk, return_kv=True)
            if cfg.block_type == "parallel":
                m, _, _ = _ffn(p, hh, cfg)
                x = x + a + m
            else:
                x = x + a
                m, _, _ = _ffn(p, apply_norm(cfg, p["ln2"], x), cfg)
                x = x + m
            rows = []
            for t in kv:          # each cache leaf's rows, padded
                c = jnp.zeros((b, cache_len, *t.shape[2:]), dtype)
                rows.append(jax.lax.dynamic_update_slice(
                    c, t.astype(dtype), (0,) * t.ndim))
            return x, tuple(rows)

        def encdec_step(x, p):
            hh = apply_norm(cfg, p["ln1"], x)
            a, kv = attention_forward(p["attn"], hh, cfg,
                                      positions=positions, mode="causal",
                                      q_chunk=q_chunk, return_kv=True)
            x = x + a
            hh = apply_norm(cfg, p["ln_x"], x)
            ax, xkv = attention_forward(p["xattn"], hh, cfg,
                                        positions=positions, mode="cross",
                                        context=enc_out, q_chunk=q_chunk,
                                        return_kv=True)
            x = x + ax
            x = x + mlp_forward(p["mlp"], apply_norm(cfg, p["ln2"], x))
            k_c = jnp.zeros(_kv_shape(cfg, b, cache_len), dtype)
            k_c = jax.lax.dynamic_update_slice(k_c, kv[0].astype(dtype),
                                               (0, 0, 0, 0))
            v_c = jnp.zeros(_kv_shape(cfg, b, cache_len), dtype)
            v_c = jax.lax.dynamic_update_slice(v_c, kv[1].astype(dtype),
                                               (0, 0, 0, 0))
            return x, (k_c, v_c, xkv[0].astype(dtype), xkv[1].astype(dtype))

        if cfg.is_encdec:
            fn = jax.checkpoint(encdec_step) if cfg.remat else encdec_step
            x, (ks, vs, xks, xvs) = jax.lax.scan(fn, x, params["layers"])
            cache.update(k=ks, v=vs, cross_k=xks, cross_v=xvs)
        else:
            fn = jax.checkpoint(dense_step) if cfg.remat else dense_step
            for name, prefix, _ in layer_stacks(cfg):
                x, rows = jax.lax.scan(fn, x, params[name])
                cache.update({prefix + n: r for n, r in
                              zip(_attn_cache_shapes(cfg, 1, 1), rows)})
        h = apply_norm(cfg, params["final_norm"], x)

    w_out = output_weights(params, cfg, dtype)
    h_last = h[:, -1] if last_idx is None else h[jnp.arange(b), last_idx]
    logits = (h_last @ w_out).astype(jnp.float32)
    return constrain(logits, ("batch", "vocab")), cache
