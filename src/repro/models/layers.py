"""Shared model primitives: norms, rotary, GQA attention, MLP, MoE.

Pure-functional (param pytrees of jnp arrays); compute in bf16 with fp32
softmax/normalization; activation shardings are *logical* annotations via
``repro.distributed.sharding.constrain``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from .config import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0):
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                              jnp.float32)).astype(jnp.float32)


def _split(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, d: int):
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm_type == "layernorm":
        p = {"scale": jnp.ones((d,), jnp.float32)}
        if cfg.use_bias:
            p["bias"] = jnp.zeros((d,), jnp.float32)
        return p
    if cfg.norm_type == "nonparametric_ln":   # OLMo
        return {}
    raise ValueError(cfg.norm_type)


def apply_norm(cfg: ModelConfig, params, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        nrm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (nrm * params["scale"]).astype(x.dtype)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    nrm = (xf - mean) * jax.lax.rsqrt(var + eps)
    if cfg.norm_type == "layernorm":
        nrm = nrm * params["scale"]
        if "bias" in params:
            nrm = nrm + params["bias"]
    return nrm.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq: jax.Array | None = None) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32.  Rotate-half form;
    ``inv_freq`` (D/2,) replaces the plain ``theta`` frequencies."""
    d = x.shape[-1]
    half = d // 2
    freq = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
            if inv_freq is None else inv_freq)
    angles = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_ramp(cfg: ModelConfig) -> tuple[int, int]:
    """First and last rotary frequency index of YaRN's ramp between the
    extrapolated and the interpolated frequencies (DeepSeek-V2's
    ``yarn_find_correction_range``)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta

    def corr(rotations):
        return (dim * math.log(cfg.yarn_original_max_pos
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.yarn_beta_slow)), dim - 1)
    return low, high


def yarn_inv_freq(cfg: ModelConfig) -> jax.Array:
    """YaRN inverse frequencies of the ``qk_rope_head_dim`` rotary dims:
    the plain ones below the ramp, divided by ``yarn_factor`` above it,
    blended linearly across it."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / cfg.yarn_factor
    low, high = yarn_ramp(cfg)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(query width), times YaRN's mscale squared."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) ** 2
    return scale


def _mla_rope(cfg: ModelConfig, x, positions):
    inv = yarn_inv_freq(cfg) if cfg.yarn_factor else None
    return rope(x, positions, cfg.rope_theta, inv)


# ---------------------------------------------------------------------------
# attention (GQA, full / local-window / cross; train + decode paths)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, *, d_model: int | None = None,
                   n_heads: int | None = None, n_kv: int | None = None):
    if cfg.attention == "mla":
        return _init_mla(key, cfg)
    d = d_model or cfg.d_model
    h = n_heads or cfg.n_heads
    kv = n_kv or cfg.n_kv_heads
    hd = cfg.head_dim
    ks = _split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd)),
        "wk": dense_init(ks[1], (d, kv * hd)),
        "wv": dense_init(ks[2], (d, kv * hd)),
        "wo": dense_init(ks[3], (h * hd, d), in_axis=0),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((h * hd,), jnp.float32)
        p["bk"] = jnp.zeros((kv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((kv * hd,), jnp.float32)
        p["bo"] = jnp.zeros((d,), jnp.float32)
    return p


def _project_qkv(p, x, cfg: ModelConfig, n_heads, n_kv, dtype):
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = x @ p["wq"].astype(dtype)
    k = x @ p["wk"].astype(dtype)
    v = x @ p["wv"].astype(dtype)
    if "bq" in p:
        q, k, v = (q + p["bq"].astype(dtype), k + p["bk"].astype(dtype),
                   v + p["bv"].astype(dtype))
    q = q.reshape(b, s, n_heads, hd)
    k = k.reshape(b, s, n_kv, hd)
    v = v.reshape(b, s, n_kv, hd)
    return q, k, v


def _sdpa(q, k, v, mask, *, q_chunk: int = 1024, scale: float | None = None):
    """Chunked scaled-dot-product attention (GQA) with fp32 softmax.

    q, k: (B, Sq|Sk, H|KV, D); v: (B, Sk, KV, Dv); mask(q_pos, k_pos)
    callable returning a boolean (Bq, Sk) block, or None.  ``scale``
    defaults to 1/sqrt(D).  Scanning over query chunks keeps the live score
    tensor at (B, H, q_chunk, Sk).
    """
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kv
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    qg = q.reshape(b, sq, kv, g, d)
    # (B, KV, G, Sq, Sk) einsum operands
    kT = k.transpose(0, 2, 3, 1)                      # (B, KV, D, Sk)

    def block(q_blk, q_pos):
        # q_blk: (B, C, KV, G, D).  No sharding constraint on the scores:
        # GQA head counts (56, 96, 10, ...) rarely divide the model axis, and
        # forcing heads->model here made GSPMD insert "involuntary full
        # rematerialization" copies (+70 GiB/device on deepseek train_4k) --
        # propagation from the projections picks a consistent (kv, g) split.
        scores = jnp.einsum("bckgd,bkds->bkgcs", q_blk, kT,
                            preferred_element_type=jnp.float32) * scale
        if mask is not None:
            m = mask(q_pos)                            # (C, Sk) bool
            scores = jnp.where(m[None, None, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgcs,bskd->bckgd", w.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.astype(q.dtype)

    if sq % q_chunk != 0:
        # largest divisor of sq not exceeding q_chunk (llava's 576+text
        # sequences are not power-of-two); tiny divisors -> single block
        q_chunk = max((c for c in range(1, q_chunk + 1) if sq % c == 0),
                      default=sq)
        if q_chunk < 64:
            q_chunk = sq
    if sq <= q_chunk:
        out = block(qg, jnp.arange(sq))
    else:
        n_blk = sq // q_chunk
        qb = qg.reshape(b, n_blk, q_chunk, kv, g, d).transpose(1, 0, 2, 3, 4, 5)
        pos = jnp.arange(sq).reshape(n_blk, q_chunk)

        def body(_, inp):
            qq, pp = inp
            return None, block(qq, pp)

        _, outs = jax.lax.scan(body, None, (qb, pos))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, kv, g, dv)
    return out.reshape(b, sq, h, dv)


def attention_forward(p, x, cfg: ModelConfig, *, positions, mode: str,
                      window: int = 0, n_heads=None, n_kv=None,
                      context=None, q_chunk: int = 1024,
                      return_kv: bool = False):
    """mode: causal | local | bidir | cross (context = encoder output).
    With ``return_kv`` also the rows the decode cache keeps (GQA: keys and
    values; MLA: the latent and the roped key)."""
    if cfg.attention == "mla":
        return mla_forward(p, x, cfg, positions=positions, q_chunk=q_chunk,
                           return_kv=return_kv)
    dtype = x.dtype
    h = n_heads or cfg.n_heads
    kv = n_kv or cfg.n_kv_heads
    if mode == "cross":
        b, s, _ = x.shape
        hd = cfg.head_dim
        q = (x @ p["wq"].astype(dtype)).reshape(b, s, h, hd)
        sk = context.shape[1]
        k = (context @ p["wk"].astype(dtype)).reshape(b, sk, kv, hd)
        v = (context @ p["wv"].astype(dtype)).reshape(b, sk, kv, hd)
        mask = None
    else:
        q, k, v = _project_qkv(p, x, cfg, h, kv, dtype)
        if mode != "bidir":
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        sk = k.shape[1]
        kpos = jnp.arange(sk)
        if mode == "causal":
            mask = lambda qp: qp[:, None] >= kpos[None, :]
        elif mode == "local":
            mask = lambda qp: ((qp[:, None] >= kpos[None, :]) &
                               (qp[:, None] - kpos[None, :] < window))
        elif mode == "bidir":
            mask = None
        else:
            raise ValueError(mode)
    q = constrain(q, ("batch", "seq", None, None))
    out = _sdpa(q, k, v, mask, q_chunk=q_chunk)
    out = out.reshape(*out.shape[:2], -1)
    out = out @ p["wo"].astype(dtype)
    if "bo" in p:
        out = out + p["bo"].astype(dtype)
    out = constrain(out, ("batch", "seq", "embed"))
    if return_kv:
        return out, (k, v)
    return out


def attention_decode(p, x, cache, cfg: ModelConfig, *, pos, window: int = 0,
                     n_heads=None, n_kv=None, cross_kv=None):
    """One-token decode. cache = {"k","v"}: (B, S_cache, KV, D); ``pos`` is
    the absolute position, either a scalar int32 shared by the batch or a
    per-row (B,) vector (continuous batching: every slot decodes at its own
    position).  For ``window>0`` the cache is a rolling buffer of length
    ``window``.  ``cross_kv`` short-circuits to cross-attention against
    precomputed encoder K/V.  An MLA layer's cache is {"c_kv", "k_pe"}
    (``mla_decode``)."""
    if cfg.attention == "mla":
        return mla_decode(p, x, cache, cfg, pos=pos)
    dtype = x.dtype
    h = n_heads or cfg.n_heads
    kv = n_kv or cfg.n_kv_heads
    hd = cfg.head_dim
    b = x.shape[0]
    if cross_kv is not None:
        q = (x @ p["wq"].astype(dtype)).reshape(b, 1, h, hd)
        k, v = cross_kv
        valid = None
        new_cache = cache
    else:
        q, k_new, v_new = _project_qkv(p, x, cfg, h, kv, dtype)
        per_row = jnp.ndim(pos) > 0
        posb = (jnp.reshape(pos, (b, 1)).astype(jnp.int32) if per_row
                else jnp.full((b, 1), pos, jnp.int32))
        q = rope(q, posb, cfg.rope_theta)
        k_new = rope(k_new, posb, cfg.rope_theta)
        s_cache = cache["k"].shape[1]
        idx = jnp.arange(s_cache)
        if per_row:
            slot = posb[:, 0] % window if window else posb[:, 0]

            def upd(c, u, s):
                return jax.lax.dynamic_update_slice(c, u.astype(c.dtype),
                                                    (s, 0, 0))

            k = jax.vmap(upd)(cache["k"], k_new, slot)
            v = jax.vmap(upd)(cache["v"], v_new, slot)
            if window:
                valid = (idx[None] <= posb % window) | (posb >= window)
                valid = valid & (idx[None] < window)
            else:
                valid = idx[None] <= posb                    # (B, S_cache)
            valid = valid[:, None, None, :]
        else:
            slot = pos % window if window else pos
            k = jax.lax.dynamic_update_slice(
                cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
            v = jax.lax.dynamic_update_slice(
                cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
            if window:
                valid = (idx <= pos % window) | (pos >= window)
                valid = valid & (idx < window)
            else:
                valid = idx <= pos
            valid = valid[None, None, None]
        new_cache = {"k": k, "v": v}
    g = h // kv
    qg = q.reshape(b, kv, g, hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(dtype),
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(hd)
    # flash-decoding split: the cache *sequence* lives on the model axis
    # (GQA head counts rarely divide 16; seq_len always does)
    scores = constrain(scores, ("batch", "kv_heads", None, "kv_seq"))
    if valid is not None:
        scores = jnp.where(valid, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w.astype(dtype), v.astype(dtype),
                     preferred_element_type=jnp.float32).astype(dtype)
    out = out.reshape(b, 1, h * hd)
    out = out @ p["wo"].astype(dtype)
    if "bo" in p:
        out = out + p["bo"].astype(dtype)
    return out, new_cache


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section 2.1)
# ---------------------------------------------------------------------------

def _init_mla(key, cfg: ModelConfig):
    d, h, c = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, r, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = _split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h * (nope + r))),
        "wkv_a": dense_init(ks[1], (d, c + r)),
        "kv_norm": {"scale": jnp.ones((c,), jnp.float32)},
        "wkv_b": dense_init(ks[2], (c, h * (nope + vd))),
        "wo": dense_init(ks[3], (h * vd, d)),
    }


def _mla_project(p, x, cfg: ModelConfig, positions):
    """Per-head queries (nope part, roped part), the normalised latent and
    the roped key shared by all heads: (B,S,H,nope), (B,S,H,r), (B,S,C),
    (B,S,r)."""
    b, s, _ = x.shape
    dtype = x.dtype
    h, c = cfg.n_heads, cfg.kv_lora_rank
    nope, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (x @ p["wq"].astype(dtype)).reshape(b, s, h, nope + r)
    kv_a = x @ p["wkv_a"].astype(dtype)
    c_kv = apply_norm(cfg, p["kv_norm"], kv_a[..., :c])
    k_pe = _mla_rope(cfg, kv_a[..., c:].reshape(b, s, 1, r), positions)
    q_pe = _mla_rope(cfg, q[..., nope:], positions)
    return q[..., :nope], q_pe, c_kv, k_pe[:, :, 0]


def mla_forward(p, x, cfg: ModelConfig, *, positions, q_chunk: int = 1024,
                return_kv: bool = False):
    """Causal MLA over a whole sequence (prefill, training): keys and
    values expanded per head from the latent, ``W_kv_b``."""
    with jax.named_scope("mla"):
        b, s, _ = x.shape
        dtype = x.dtype
        h, nope, vd = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
        q_nope, q_pe, c_kv, k_pe = _mla_project(p, x, cfg, positions)
        kv = (c_kv @ p["wkv_b"].astype(dtype)).reshape(b, s, h, nope + vd)
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe[:, :, None], (*k_pe.shape[:2], h,
                                                 k_pe.shape[-1]))], -1)
        kpos = jnp.arange(s)
        q = constrain(q, ("batch", "seq", None, None))
        out = _sdpa(q, k, kv[..., nope:],
                    lambda qp: qp[:, None] >= kpos[None, :],
                    q_chunk=q_chunk, scale=mla_softmax_scale(cfg))
        out = out.reshape(b, s, h * vd) @ p["wo"].astype(dtype)
        out = constrain(out, ("batch", "seq", "embed"))
    if return_kv:
        return out, (c_kv, k_pe)
    return out


def mla_decode(p, x, cache, cfg: ModelConfig, *, pos):
    """One-token MLA decode, absorbed: ``W_uk`` folds into the query and
    ``W_uv`` into the output, so scores and values are taken against the
    cached latent itself, never expanded over the cache.  cache =
    {"c_kv": (B, S, C), "k_pe": (B, S, r)}; ``pos`` a scalar or (B,)."""
    with jax.named_scope("mla"):
        dtype = x.dtype
        b = x.shape[0]
        h, c = cfg.n_heads, cfg.kv_lora_rank
        nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
        posb = jnp.broadcast_to(jnp.reshape(pos, (-1, 1)), (b, 1)
                                ).astype(jnp.int32)
        q_nope, q_pe, c_new, kpe_new = _mla_project(p, x, cfg, posb)

        def upd(cc, u, at):
            return jax.lax.dynamic_update_slice(cc, u.astype(cc.dtype),
                                                (at, 0))

        c_kv = jax.vmap(upd)(cache["c_kv"], c_new, posb[:, 0])
        k_pe = jax.vmap(upd)(cache["k_pe"], kpe_new, posb[:, 0])
        wkv_b = p["wkv_b"].astype(dtype).reshape(c, h, nope + vd)
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], wkv_b[..., :nope])
        scores = (jnp.einsum("bhc,bsc->bhs", q_lat, c_kv.astype(dtype),
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bhr,bsr->bhs", q_pe[:, 0],
                               k_pe.astype(dtype),
                               preferred_element_type=jnp.float32))
        scores = scores * mla_softmax_scale(cfg)
        scores = constrain(scores, ("batch", None, "kv_seq"))
        valid = jnp.arange(c_kv.shape[1])[None] <= posb        # (B, S)
        scores = jnp.where(valid[:, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        o_lat = jnp.einsum("bhs,bsc->bhc", w.astype(dtype),
                           c_kv.astype(dtype),
                           preferred_element_type=jnp.float32).astype(dtype)
        o = jnp.einsum("bhc,chv->bhv", o_lat, wkv_b[..., nope:])
        out = o.reshape(b, 1, h * vd) @ p["wo"].astype(dtype)
    return out, {"c_kv": c_kv, "k_pe": k_pe}


# ---------------------------------------------------------------------------
# MLP (SwiGLU) and MoE
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d: int | None = None,
             ff: int | None = None):
    d = d or cfg.d_model
    ff = ff or cfg.d_ff
    ks = _split(key, 3)
    if cfg.mlp_type == "gelu":
        p = {"w_up": dense_init(ks[1], (d, ff)),
             "w_down": dense_init(ks[2], (ff, d))}
        if cfg.use_bias:
            p["b_up"] = jnp.zeros((ff,), jnp.float32)
            p["b_down"] = jnp.zeros((d,), jnp.float32)
        return p
    return {
        "w_gate": dense_init(ks[0], (d, ff)),
        "w_up": dense_init(ks[1], (d, ff)),
        "w_down": dense_init(ks[2], (ff, d)),
    }


def mlp_forward(p, x):
    dtype = x.dtype
    if "w_gate" not in p:                       # gelu MLP (whisper)
        h = x @ p["w_up"].astype(dtype)
        if "b_up" in p:
            h = h + p["b_up"].astype(dtype)
        h = constrain(jax.nn.gelu(h), ("batch", "seq", "mlp"))
        out = h @ p["w_down"].astype(dtype)
        if "b_down" in p:
            out = out + p["b_down"].astype(dtype)
        return constrain(out, ("batch", "seq", "embed"))
    gate = jax.nn.silu(x @ p["w_gate"].astype(dtype))
    up = x @ p["w_up"].astype(dtype)
    h = constrain(gate * up, ("batch", "seq", "mlp"))
    return constrain(h @ p["w_down"].astype(dtype), ("batch", "seq", "embed"))


def init_moe(key, cfg: ModelConfig):
    ks = _split(key, 4)
    e, d, ff = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    p = {
        "router": dense_init(ks[0], (d, cfg.router_width)),
        "w_gate": dense_init(ks[1], (e, d, ff), in_axis=1),
        "w_up": dense_init(ks[2], (e, d, ff), in_axis=1),
        "w_down": dense_init(ks[3], (e, ff, d), in_axis=1),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(jax.random.fold_in(key, 4), cfg,
                               ff=cfg.n_shared_experts * ff)
    return p


MOE_GROUP = 2048  # tokens per dispatch group (GShard-style local capacity)


def moe_forward(p, x, cfg: ModelConfig):
    """The expert layer.  Returns (out, aux_loss, routes): ``routes``
    (B, S, n_experts) bool marks the token -> held-expert assignments that
    were computed."""
    with jax.named_scope("moe"):
        if cfg.moe_dropless:
            return _moe_dropless(p, x, cfg)
        return _moe_capacity(p, x, cfg)


def _moe_dropless(p, x, cfg: ModelConfig):
    """DeepSeekMoE as one chip of an expert-parallel deployment holds it.

    A softmax router over all ``router_width`` experts picks the top-k
    (greedy), gates optionally renormalised; this layer holds
    experts ``[expert_offset, expert_offset + n_experts)`` and adds their
    gate-weighted outputs: every held expert runs on every token and a
    token's gate is 0 for a held expert it was not routed to, so no token
    is ever dropped and what an absent expert would add is left out.  The
    shared experts, one SwiGLU every token passes through, are added
    whole."""
    dtype = x.dtype
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    logits = (xt @ p["router"].astype(dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                        # (N, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, cfg.top_k)          # (N, k)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    held = cfg.expert_offset + jnp.arange(cfg.n_experts)
    hit = gate_idx[:, :, None] == held                             # (N,k,e)
    routes = jnp.any(hit, axis=1)                                  # (N, e)
    gates = jnp.sum(jnp.where(hit, gate_vals[..., None], 0.0), axis=1)
    gate = jax.nn.silu(jnp.einsum("nd,edf->nef", xt,
                                  p["w_gate"].astype(dtype)))
    up = jnp.einsum("nd,edf->nef", xt, p["w_up"].astype(dtype))
    hidden = gate * up * gates[..., None].astype(dtype)
    out = jnp.einsum("nef,efd->nd", hidden, p["w_down"].astype(dtype))
    out = out.reshape(b, s, d)
    if "shared" in p:
        out = out + mlp_forward(p["shared"], x)
    # load-balancing auxiliary loss (Switch) over the router's experts
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_idx, cfg.router_width).sum(1), axis=0)
    aux = cfg.router_width * jnp.sum(me * ce)
    return (constrain(out, ("batch", "seq", "embed")), aux,
            routes.reshape(b, s, -1))


def _moe_capacity(p, x, cfg: ModelConfig):
    """GShard-style grouped top-k dispatch with capacity.

    Tokens are dispatched within *groups* of <= MOE_GROUP tokens (per
    sequence slice), so the one-hot dispatch/combine tensors are
    (G_count, G, E, C_g) with C_g = ceil(G*k/E*cf) -- never the quadratic
    (N, E, N*k/E) blow-up of a global dispatch.  A token past its expert's
    capacity is dropped.
    """
    dtype = x.dtype
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    if s >= MOE_GROUP and s % MOE_GROUP == 0:
        g_count, g = b * (s // MOE_GROUP), MOE_GROUP
    elif s == 1:
        g_count, g = 1, b       # decode: one group across the batch
    else:
        g_count, g = b, s
    xt = x.reshape(g_count, g, d)
    logits = (xt @ p["router"].astype(dtype)).astype(jnp.float32)  # (B,G,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                  # (B,G,k)
    if cfg.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    cap = int(math.ceil(g * k / e * cfg.capacity_factor))
    cap = max(cap, 4)
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)        # (B,G,k,E)
    flat = onehot.reshape(g_count, g * k, e)
    pos_in_e = (jnp.cumsum(flat, axis=1) - flat).reshape(g_count, g, k, e)
    pos = jnp.sum(pos_in_e * onehot, axis=-1)                      # (B,G,k)
    keep = (pos < cap).astype(jnp.float32)
    pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32)           # (B,G,k,C)
    disp = jnp.einsum("bgke,bgkc->bgec", onehot, pos_oh * keep[..., None])
    comb = jnp.einsum("bgec,bgk->bgec", disp, gate_vals.astype(jnp.float32))
    xe = jnp.einsum("bgd,bgec->becd", xt.astype(jnp.float32),
                    disp).astype(dtype)                            # (B,E,C,D)
    xe = constrain(xe, (None, "experts", "expert_capacity", "embed"))
    gate = jax.nn.silu(jnp.einsum("becd,edf->becf", xe,
                                  p["w_gate"].astype(dtype)))
    up = jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(dtype))
    ye = jnp.einsum("becf,efd->becd", gate * up, p["w_down"].astype(dtype))
    ye = constrain(ye, (None, "experts", "expert_capacity", "embed"))
    out = jnp.einsum("becd,bgec->bgd", ye.astype(jnp.float32),
                     comb).astype(dtype)
    # load-balancing auxiliary loss (Switch)
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(onehot.sum(2), axis=(0, 1))
    aux = e * jnp.sum(me * ce)
    routes = jnp.any(onehot * keep[..., None] > 0, axis=2)         # (B,G,E)
    return (constrain(out.reshape(b, s, d), ("batch", "seq", "embed")), aux,
            routes.reshape(b, s, e))
