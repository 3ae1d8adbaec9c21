"""Plain float32 reference of DeepSeek-V2-Lite as one chip of an
expert-parallel deployment holds it, from the published description.

DeepSeek-V2 (arXiv:2405.04434, Sections 2.1 and 2.2) and the published
``config.json`` of DeepSeek-V2-Lite: a decoder-only transformer without
biases; RMSNorm (eps 1e-6) with a learned scale before attention, before
the feed-forward block and at the end; an untied output head.

* Multi-head latent attention with no query compression: per head a query
  of ``qk_nope_head_dim`` + ``qk_rope_head_dim`` values from ``x @ wq``;
  ``x @ wkv_a`` gives a latent of ``kv_lora_rank`` values, RMS-normalised
  (its own scale), and one key of ``qk_rope_head_dim`` values shared by all
  heads; ``latent @ wkv_b`` expands the latent into each head's unroped key
  part and its value (``v_head_dim``).  Written in that expanded form here.
  Scores are scaled by 1/sqrt(qk_nope + qk_rope) times YaRN's mscale
  squared, ``(0.1 * mscale_all_dim * ln(factor) + 1) ** 2`` (the
  configuration's ``yarn_mscale``).
* Rotary embedding on the ``qk_rope_head_dim`` values only, with YaRN's
  inverse frequencies: the plain ones (base ``rope_theta``) below a linear
  ramp over the frequency indices that ``beta_fast`` and ``beta_slow``
  rotations over ``original_max_position_embeddings`` give, divided by
  ``factor`` above it; the cos/sin factor mscale / mscale_all_dim is 1 for
  the published values.  Departure: rotate-half form, where the published
  code first de-interleaves the rope dims (x[0::2], x[1::2]); the two are
  the same model up to a fixed permutation of the rope columns of ``wq``
  and ``wkv_a``.
* The first ``n_dense_layers`` layers have a SwiGLU of ``d_ff``; every
  later one is DeepSeekMoE: a softmax over all ``n_routed_experts``
  router logits, the greedy top-``top_k`` gates (renormalised only where
  ``norm_topk_prob``; the published scaling factor is 1); routed experts are
  SwiGLUs of ``moe_d_ff``; the shared experts, one SwiGLU of
  ``n_shared_experts * moe_d_ff``, take every token.
* The chip's share: of the routed experts it holds ``n_experts`` from
  ``expert_offset`` on, and adds only their gate-weighted outputs; what the
  absent experts would add is left out, as in the program.  The shared
  experts are added whole.

Straightforward ``jax.numpy``, every matmul at HIGHEST precision, one
sequence at a time; attention in blocks of query rows and the expert layer
in blocks of tokens so that a long sequence fits beside the weights.  It
imports nothing of the program under test.

``init`` makes the weights from the seed in one jitted call, in the layout
the served path takes: the leading dense layers stacked under
``dense_layers``, the expert layers under ``layers``, ``x @ w``
orientation, norm scales of one; truncated normals in (-2, 2) standard
deviations scaled by 1 / sqrt(fan_in).

``forward(w, m, tokens, dtype=...)`` gives float32 logits for every
position; ``dtype="float8_e4m3fn"`` rounds both operands of every matmul
to that type (per-tensor scale, float32 accumulation): the lower-precision
control that the correctness limit must reject.

The counts (``harness/spec.py``) are of the algorithm as deployed: MLA
expanded at prefill and absorbed at decode (``W_uk`` folded into the
query, ``W_uv`` into the output, scores and values taken against the
cached latent), and the routed experts at their expectation of
``top_k * n_experts / n_routed_experts`` held experts a token.
"""
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
BF16_BYTES = 2
EXPERTS_ACTIVE = "serve_moe_experts_active_total"


def _dims(m):
    return (m["d_model"], m["n_heads"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"])


def _moe_layers(m):
    return m["n_layers"] - m["n_dense_layers"]


def init(key, m):
    d, h, c, nope, r, vd = _dims(m)
    ff, f, e = m["d_ff"], m["moe_d_ff"], m["n_experts"]
    fs = m["n_shared_experts"] * f
    v = m["vocab_size"]
    ks = iter(jax.random.split(key, 24))

    def w(shape, fan_in):
        return jax.random.truncated_normal(
            next(ks), -2.0, 2.0, shape, jnp.float32) / math.sqrt(fan_in)

    def ones(*shape):
        return {"scale": jnp.ones(shape, jnp.float32)}

    def swiglu(n, width):
        return {"w_gate": w((n, d, width), d), "w_up": w((n, d, width), d),
                "w_down": w((n, width, d), width)}

    def layers(n):
        return {"ln1": ones(n, d), "ln2": ones(n, d),
                "attn": {"wq": w((n, d, h * (nope + r)), d),
                         "wkv_a": w((n, d, c + r), d),
                         "kv_norm": ones(n, c),
                         "wkv_b": w((n, c, h * (nope + vd)), c),
                         "wo": w((n, h * vd, d), h * vd)}}

    nd, nm = m["n_dense_layers"], _moe_layers(m)
    out = {"embed": w((v, d), d), "lm_head": w((d, v), d),
           "final_norm": ones(d), "layers": layers(nm)}
    out["layers"]["moe"] = {
        "router": w((nm, d, m["n_routed_experts"]), d),
        "w_gate": w((nm, e, d, f), d), "w_up": w((nm, e, d, f), d),
        "w_down": w((nm, e, f, d), f), "shared": swiglu(nm, fs)}
    if nd:
        out["dense_layers"] = dict(layers(nd), mlp=swiglu(nd, ff))
    return out


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


def _mscale(m):
    return 0.1 * m["yarn_mscale"] * math.log(m["yarn_factor"]) + 1.0


def yarn_inv_freq(m):
    """YaRN's inverse frequencies of the rope dims, and its ramp's ends."""
    dim, base = m["qk_rope_head_dim"], m["rope_theta"]

    def corr(rotations):     # rope dim that turns ``rotations`` times
        return (dim * math.log(m["yarn_original_max_pos"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(m["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(m["yarn_beta_slow"])), dim - 1)
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return extra / m["yarn_factor"] * ramp + extra * (1.0 - ramp), (low, high)


def softmax_scale(m):
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 \
        * _mscale(m) ** 2


def _rope(x, pos, inv):
    """x: (S, H, D); rotate-half form."""
    half = x.shape[-1] // 2
    ang = pos[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(w, m, tokens, *, dtype="float32"):
    """tokens (S,) int32 -> logits (S, V) float32, causal."""
    d, h, c, nope, r, vd = _dims(m)
    eps = m["norm_eps"]
    e0, e = m.get("expert_offset", 0), m["n_experts"]
    rnd = _rounder(dtype)

    def mm(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b), precision=HIGHEST)

    s = tokens.shape[0]
    pos = jnp.arange(s)
    inv, _ = yarn_inv_freq(m)
    scale = softmax_scale(m)
    qb = math.gcd(s, 512)   # query rows and tokens a block
    x = w["embed"][tokens]

    def attention(x, p):
        a = _rms_norm(x, p["ln1"]["scale"], eps)
        q = mm("sd,dn->sn", a, p["attn"]["wq"]).reshape(s, h, nope + r)
        kv_a = mm("sd,dn->sn", a, p["attn"]["wkv_a"])
        latent = _rms_norm(kv_a[:, :c], p["attn"]["kv_norm"]["scale"], eps)
        k_pe = _rope(kv_a[:, None, c:], pos.astype(jnp.float32), inv)
        kv = mm("sc,cn->sn", latent, p["attn"]["wkv_b"]).reshape(
            s, h, nope + vd)
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], pos.astype(jnp.float32),
                                  inv)], -1)
        k = jnp.concatenate([kv[..., :nope],
                             jnp.broadcast_to(k_pe, (s, h, r))], -1)
        val = kv[..., nope:]

        def rows(i):   # attention of query rows [i * qb, (i + 1) * qb)
            qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
            scores = mm("qhd,khd->hqk", qi, k) * scale
            causal = (i * qb + jnp.arange(qb))[:, None] >= pos[None, :]
            probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return mm("hqk,khd->qhd", probs, val)
        o = jax.lax.map(rows, jnp.arange(s // qb)).reshape(s, h * vd)
        x = x + mm("sn,nd->sd", o, p["attn"]["wo"])
        return x, _rms_norm(x, p["ln2"]["scale"], eps)

    def swiglu(b, p):
        g = jax.nn.silu(mm("sd,df->sf", b, p["w_gate"]))
        return mm("sf,fd->sd", g * mm("sd,df->sf", b, p["w_up"]),
                  p["w_down"])

    def experts(b, p):
        """The held experts' gate-weighted outputs, tokens in blocks."""
        def block(bb):
            probs = jax.nn.softmax(mm("sd,de->se", bb, p["router"]), -1)
            top, idx = jax.lax.top_k(probs, m["top_k"])
            if m["norm_topk_prob"]:
                top = top / jnp.sum(top, -1, keepdims=True)
            gates = jnp.zeros_like(probs).at[
                jnp.arange(bb.shape[0])[:, None], idx].set(top)
            gates = gates[:, e0:e0 + e]
            g = jax.nn.silu(mm("sd,edf->sef", bb, p["w_gate"]))
            u = mm("sd,edf->sef", bb, p["w_up"])
            y = mm("sef,efd->sed", g * u, p["w_down"])
            return jnp.einsum("se,sed->sd", gates, y, precision=HIGHEST)
        return jax.lax.map(block, b.reshape(s // qb, qb, d)).reshape(s, d)

    def dense_layer(x, p):
        x, b = attention(x, p)
        return x + swiglu(b, p["mlp"]), None

    def moe_layer(x, p):
        x, b = attention(x, p)
        return x + experts(b, p["moe"]) + swiglu(b, p["moe"]["shared"]), None

    if "dense_layers" in w:
        x, _ = jax.lax.scan(dense_layer, x, w["dense_layers"])
    x, _ = jax.lax.scan(moe_layer, x, w["layers"])
    x = _rms_norm(x, w["final_norm"]["scale"], eps)
    return mm("sd,dv->sv", x, w["lm_head"])


# ---------------------------------------------------------------------------
# counts (harness/spec.py): operations and least bytes of the algorithm
# ---------------------------------------------------------------------------

def attention_params(m):
    d, h, c, nope, r, vd = _dims(m)
    return (d * h * (nope + r) + d * (c + r) + c + c * h * (nope + vd)
            + h * vd * d)


def _expert_params(m):
    return 3 * m["d_model"] * m["moe_d_ff"]


def _routed_per_token(m):
    """Held experts a token reaches, on average: top_k * held / routed."""
    return m["top_k"] * m["n_experts"] / m["n_routed_experts"]


def _ffn_flops_per_token(m):
    d = m["d_model"]
    dense = m["n_dense_layers"] * 3 * d * m["d_ff"]
    moe = _moe_layers(m) * (d * m["n_routed_experts"]
                            + m["n_shared_experts"] * _expert_params(m)
                            + _routed_per_token(m) * _expert_params(m))
    return 2.0 * (dense + moe)


def prefill_flops(m, prompt_len):
    """Causal prefill of the true prompt, MLA expanded (keys and values
    from the latent per head); one logits row."""
    d, h, c, nope, r, vd = _dims(m)
    p = int(prompt_len)
    pairs = p * (p + 1) // 2
    per_token = (2.0 * m["n_layers"] * attention_params(m)
                 + _ffn_flops_per_token(m))
    attend = 2.0 * m["n_layers"] * h * (nope + r + vd) * pairs
    return p * per_token + attend + 2.0 * d * m["vocab_size"]


def decode_flops(m, attended):
    """One decoded token attending ``attended`` positions, MLA absorbed:
    the query's latent (``W_uk``), scores against the latent and the roped
    key, values from the latent, then ``W_uv``."""
    d, h, c, nope, r, vd = _dims(m)
    a = int(attended)
    proj = d * h * (nope + r) + d * (c + r) + h * nope * c + h * c * vd \
        + h * vd * d
    attend = h * (c + r) * a + h * c * a
    return (2.0 * m["n_layers"] * (proj + attend) + _ffn_flops_per_token(m)
            + 2.0 * d * m["vocab_size"])


def decode_least_bytes(m, attended, counters):
    """Least HBM bytes of one batched decode tick over live slots that
    attend ``attended`` positions each: in bf16, every weight once but the
    routed experts, of which only the held ones the tick's
    ``serve_moe_experts_active_total`` counts as having computed a live
    token (summed over the expert layers), the output head, the norms and
    the batch's embedding rows; each slot's cached latent and roped key
    before this token (``kv_lora_rank + qk_rope_head_dim`` values a
    position a layer), and each slot's one new row of them."""
    d, h, c, nope, r, vd = _dims(m)
    nd, nm = m["n_dense_layers"], _moe_layers(m)
    active = (counters or {}).get(EXPERTS_ACTIVE, 0.0)
    weights = (m["n_layers"] * (attention_params(m) + 2 * d)
               + nd * 3 * d * m["d_ff"]
               + nm * (d * m["n_routed_experts"]
                       + m["n_shared_experts"] * _expert_params(m))
               + active * _expert_params(m)
               + d + m["vocab_size"] * d + len(attended) * d)
    row = m["n_layers"] * (c + r)
    cached = sum(max(int(a) - 1, 0) for a in attended)
    return BF16_BYTES * (weights + row * cached + row * len(attended))
