"""Plain float32 reference of deepseek-coder-33b, from the published
description.

DeepSeek-Coder (arXiv:2401.14196, Section 3.4 and Table 2) uses the Llama
architecture: a decoder-only transformer without biases; RMSNorm with a
learned scale before attention, before the MLP and at the end; rotary
position embeddings (rotate-half form, base 100000; the published model
also divides positions by a linear scaling factor of 4, which the served
program does not, so the factor is the configuration's ``rope_scaling``
and is 1 as run); grouped-query attention, 56 query heads over 8
key/value heads; a SwiGLU MLP; an untied output head.  Written in
straightforward ``jax.numpy`` with every matmul at HIGHEST precision, one
sequence at a time, attention in blocks of query rows so that a long
sequence fits beside the weights.  It imports nothing of the program under test.

``init`` makes the weights from the seed in one jitted call, in the
layout the served path takes: per-layer weights stacked on a leading
layer axis, ``x @ w`` orientation, norm scales of one.  Truncated normals
in (-2, 2) standard deviations, scaled by 1 / sqrt(fan_in).

``forward(w, m, tokens, dtype=...)`` gives float32 logits for every
position.  ``dtype="float8_e4m3fn"`` rounds both operands of every matmul
to that type (per-tensor scale, float32 accumulation): the lower-precision
control that the correctness limit must reject.
"""
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(m):
    d, h, kv = m["d_model"], m["n_heads"], m["n_kv_heads"]
    return d, h, kv, m.get("head_dim") or d // h, m["d_ff"], \
        m["vocab_size"], m["n_layers"]


def init(key, m):
    d, h, kv, hd, ff, v, n = _dims(m)
    ks = jax.random.split(key, 9)

    def w(k, shape, fan_in):
        return jax.random.truncated_normal(
            k, -2.0, 2.0, shape, jnp.float32) / math.sqrt(fan_in)

    def ones(*shape):
        return {"scale": jnp.ones(shape, jnp.float32)}

    return {
        "embed": w(ks[0], (v, d), d),
        "lm_head": w(ks[8], (d, v), d),
        "final_norm": ones(d),
        "layers": {
            "ln1": ones(n, d), "ln2": ones(n, d),
            "attn": {"wq": w(ks[1], (n, d, h * hd), d),
                     "wk": w(ks[2], (n, d, kv * hd), d),
                     "wv": w(ks[3], (n, d, kv * hd), d),
                     "wo": w(ks[4], (n, h * hd, d), h * hd)},
            "mlp": {"w_gate": w(ks[5], (n, d, ff), d),
                    "w_up": w(ks[6], (n, d, ff), d),
                    "w_down": w(ks[7], (n, ff, d), ff)},
        },
    }


def _rounder(dtype):
    if dtype == "float32":
        return lambda x: x
    fmax = float(jnp.finfo(dtype).max)

    def rnd(x):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / fmax
        return (x / scale).astype(dtype).astype(jnp.float32) * scale
    return rnd


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    """x: (S, H, D); rotate-half form; ``pos`` already scaled."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(w, m, tokens, *, dtype="float32"):
    """tokens (S,) int32 -> logits (S, V) float32, causal."""
    d, h, kv, hd, ff, v, n = _dims(m)
    eps, theta = m["norm_eps"], m["rope_theta"]
    rnd = _rounder(dtype)

    def mm(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b), precision=HIGHEST)

    s = tokens.shape[0]
    pos = jnp.arange(s)
    rpos = pos.astype(jnp.float32) / m["rope_scaling"]
    qb = math.gcd(s, 512)   # query rows a block, so the scores fit
    x = w["embed"][tokens]

    def layer(x, p):
        a = _rms_norm(x, p["ln1"]["scale"], eps)
        q = mm("sd,dn->sn", a, p["attn"]["wq"]).reshape(s, h, hd)
        k = mm("sd,dn->sn", a, p["attn"]["wk"]).reshape(s, kv, hd)
        val = mm("sd,dn->sn", a, p["attn"]["wv"]).reshape(s, kv, hd)
        q, k = _rope(q, rpos, theta), _rope(k, rpos, theta)
        k = jnp.repeat(k, h // kv, axis=1)
        val = jnp.repeat(val, h // kv, axis=1)

        def rows(i):   # attention of query rows [i * qb, (i + 1) * qb)
            qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
            scores = mm("qhd,khd->hqk", qi, k) / math.sqrt(hd)
            causal = (i * qb + jnp.arange(qb))[:, None] >= pos[None, :]
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return mm("hqk,khd->qhd", probs, val)
        o = jax.lax.map(rows, jnp.arange(s // qb)).reshape(s, h * hd)
        x = x + mm("sn,nd->sd", o, p["attn"]["wo"])
        b = _rms_norm(x, p["ln2"]["scale"], eps)
        g = jax.nn.silu(mm("sd,df->sf", b, p["mlp"]["w_gate"]))
        u = mm("sd,df->sf", b, p["mlp"]["w_up"])
        return x + mm("sf,fd->sd", g * u, p["mlp"]["w_down"]), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rms_norm(x, w["final_norm"]["scale"], eps)
    return mm("sd,dv->sv", x, w["lm_head"])
