"""DeepSeek-V2-Lite's mechanisms at a tiny width on the CPU: multi-head
latent attention (absorbed decode against the latent cache), YaRN, the
expert layer as one chip of an expert-parallel deployment holds it
(dropless, shared experts, a share of the router's experts), the decode
tick's expert counts, and the parameter counts at the published sizes.
The program is held to plain per-token loops written here, and the other
MoE configurations to their logits before this expert layer existed."""
import dataclasses
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.steps import decode_moe_counts
from repro.launch.shapes import make_batch
from repro.models import layers, lm
from repro.obs import FlightRecorder, Tracer
from repro.serve import EngineConfig, Request, ServeEngine, WorkerPool
from repro.serve import uniform_policy

GOLDEN = Path(__file__).parent / "data" / "moe-tiny-logits.npz"


def _tiny(**kw):
    return dataclasses.replace(get_config("deepseek-v2-lite", tiny=True),
                               compute_dtype="float32", remat=False, **kw)


def _tokens(cfg, shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, cfg.vocab_size, shape), jnp.int32)


# ------------------------------------------------------------- attention ----

def test_absorbed_decode_equals_expanded_attention():
    """After a prefill, each absorbed decode step against the latent cache
    gives the expanded attention's output at that position, and the cache
    rows it writes are the expanded pass's latent and roped key."""
    cfg = _tiny()
    p = lm.init_params(jax.random.key(3), cfg)["layers"]
    attn = jax.tree.map(lambda a: a[0], p["attn"])
    x = jax.random.normal(jax.random.key(4), (2, 20, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(20), (2, 20))
    with jax.default_matmul_precision("highest"):
        want, (c_kv, k_pe) = layers.attention_forward(
            attn, x, cfg, positions=pos, mode="causal", return_kv=True)
        cache = {"c_kv": jnp.zeros((2, 32, cfg.kv_lora_rank)),
                 "k_pe": jnp.zeros((2, 32, cfg.qk_rope_head_dim))}
        cache = {"c_kv": cache["c_kv"].at[:, :12].set(c_kv[:, :12]),
                 "k_pe": cache["k_pe"].at[:, :12].set(k_pe[:, :12])}
        for t in range(12, 20):
            out, cache = layers.attention_decode(
                attn, x[:, t:t + 1], cache, cfg, pos=jnp.int32(t))
            np.testing.assert_allclose(np.asarray(out[:, 0]),
                                       np.asarray(want[:, t]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache["c_kv"][:, :20]),
                               np.asarray(c_kv), atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache["k_pe"][:, :20]),
                               np.asarray(k_pe), atol=1e-5)


def test_yarn_constants_match_the_published_recipe():
    cfg = get_config("deepseek-v2-lite")
    assert layers.yarn_ramp(cfg) == (10, 23)
    want = (192 ** -0.5) * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert layers.mla_softmax_scale(cfg) == pytest.approx(want, rel=1e-12)
    assert layers.mla_softmax_scale(cfg) == pytest.approx(0.114721, abs=5e-7)
    # DeepSeek-V2's yarn rotary embedding, written out
    dim, base = 64, 10000.0
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - 10) / (23 - 10), 0, 1)
    mask = 1.0 - ramp
    inv = extra / 40 * (1 - mask) + extra * mask
    np.testing.assert_allclose(np.asarray(layers.yarn_inv_freq(cfg)), inv,
                               rtol=1e-6)


# ---------------------------------------------------------- expert layer ----

def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _layer_by_loop(p, x, cfg):
    """The whole expert layer, token by token: softmax router over every
    expert, top-k, each routed expert's gate-weighted output, plus the
    shared experts.  ``p`` holds all of the router's experts."""
    out = []
    for t in np.asarray(x):
        probs = np.asarray(jax.nn.softmax(t @ np.asarray(p["router"])))
        top = np.argsort(-probs, kind="stable")[:cfg.top_k]
        gates = probs[top]
        if cfg.norm_topk_prob:
            gates = gates / gates.sum()
        y = sum(g * np.asarray(_swiglu(
            t, p["w_gate"][e], p["w_up"][e], p["w_down"][e]))
            for g, e in zip(gates, top))
        s = p["shared"]
        out.append(y + np.asarray(_swiglu(t, s["w_gate"], s["w_up"],
                                          s["w_down"])))
    return np.stack(out)


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_expert_shares_add_up_to_the_whole_layer(norm_topk_prob):
    """Each of the router's E/held shares computes its own experts' part;
    the parts, with the shared experts counted once, are the whole layer."""
    full = _tiny(n_experts=8, norm_topk_prob=norm_topk_prob)
    p = layers.init_moe(jax.random.key(5), full)
    x = jax.random.normal(jax.random.key(6), (1, 10, full.d_model))
    shared = layers.mlp_forward(p["shared"], x)
    total = shared
    with jax.default_matmul_precision("highest"):
        for off in (0, 4):
            cfg = _tiny(n_experts=4, expert_offset=off,
                        norm_topk_prob=norm_topk_prob)
            share = dict(p, **{k: p[k][off:off + 4]
                               for k in ("w_gate", "w_up", "w_down")})
            out, _, routes = layers.moe_forward(share, x, cfg)
            total = total + (out - shared)
        want = _layer_by_loop(p, x[0], full)
    np.testing.assert_allclose(np.asarray(total[0]), want, atol=1e-5)


def test_dropless_decode_rows_equal_each_row_alone():
    """A decode batch that routes every token to the same held experts
    gives each row what it gets alone; the capacity path drops rows of it."""
    cfg = _tiny()
    p = layers.init_moe(jax.random.key(7), cfg)
    # every token's largest router logit is expert 0's, the rest tie at 0
    router = jnp.zeros_like(p["router"]).at[0, 0].set(50.0)
    p = dict(p, router=router)
    x = jax.random.normal(jax.random.key(8), (16, 1, cfg.d_model))
    x = x.at[:, 0, 0].set(jnp.abs(x[:, 0, 0]) + 0.5)
    with jax.default_matmul_precision("highest"):
        batch, _, routes = layers.moe_forward(p, x, cfg)
        alone = jnp.concatenate([layers.moe_forward(p, x[i:i + 1], cfg)[0]
                                 for i in range(16)])
        assert bool(routes[:, 0, 0].all())
        np.testing.assert_allclose(np.asarray(batch), np.asarray(alone),
                                   atol=1e-6)
        capped = dataclasses.replace(cfg, moe_dropless=False,
                                     n_experts=8, n_routed_experts=0,
                                     n_shared_experts=0)
        q = layers.init_moe(jax.random.key(7), capped)
        q = dict(q, router=jnp.zeros_like(q["router"]).at[0, 0].set(50.0))
        batch = layers.moe_forward(q, x, capped)[0]
        alone = jnp.concatenate([layers.moe_forward(q, x[i:i + 1],
                                                    capped)[0]
                                 for i in range(16)])
    assert not np.allclose(np.asarray(batch), np.asarray(alone), atol=1e-3)


@pytest.mark.parametrize("arch", ["granite_moe_1b", "phi35_moe_42b"])
def test_capacity_moe_logits_are_unchanged(arch):
    """The other MoE configurations keep the grouped capacity dispatch and
    renormalised top-k: their tiny logits and loss, bit for bit, as before
    the expert layer learned to hold a share."""
    gold = np.load(GOLDEN)
    cfg = get_config(arch, tiny=True)
    params = lm.init_params(jax.random.key(0), cfg)
    toks = _tokens(cfg, (2, 16))
    pre, cache = lm.prefill(params, cfg, {"tokens": toks[:, :12]}, 32)
    dec, _ = lm.decode_step(params, cfg, cache, toks[:, 12:13],
                            jnp.asarray([12, 12], jnp.int32))
    loss, _ = lm.forward_train(
        params, cfg, {"tokens": toks, "targets": toks,
                      "loss_mask": jnp.ones(toks.shape)},
        q_chunk=16, xent_chunk=16)
    assert np.array_equal(np.asarray(pre), gold[f"{arch}.prefill"])
    assert np.array_equal(np.asarray(dec), gold[f"{arch}.decode"])
    assert np.array_equal(np.asarray(loss), gold[f"{arch}.loss"])


# ----------------------------------------------------------- whole model ----

def test_train_step_runs_and_is_finite():
    cfg = get_config("deepseek-v2-lite", tiny=True)
    params = lm.init_params(jax.random.key(0), cfg)
    batch = make_batch(cfg, batch=2, seq=32)
    loss, g = jax.value_and_grad(lambda p: lm.forward_train(
        p, cfg, batch, q_chunk=16, xent_chunk=16)[0])(params)
    assert bool(jnp.isfinite(loss)) and float(loss) > 0
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))
    assert float(jnp.abs(g["dense_layers"]["mlp"]["w_up"]).max()) > 0
    assert float(jnp.abs(g["layers"]["moe"]["shared"]["w_up"]).max()) > 0


def test_prefill_and_decode_match_the_full_forward():
    cfg = _tiny()
    params = lm.init_params(jax.random.key(1), cfg)
    toks = _tokens(cfg, (2, 24), seed=3)
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    h, _ = lm.backbone(params, cfg, params["embed"][toks], pos, q_chunk=16)
    want = h[:, -1] @ params["lm_head"]
    pre, _ = lm.prefill(params, cfg, {"tokens": toks}, 64)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(want), atol=2e-4)
    _, cache = lm.prefill(params, cfg, {"tokens": toks[:, :23]}, 64)
    assert set(cache) == {"c_kv", "k_pe", "dense_c_kv", "dense_k_pe"}
    assert cache["c_kv"].shape == (2, 2, 64, cfg.kv_lora_rank)
    assert cache["dense_k_pe"].shape == (1, 2, 64, cfg.qk_rope_head_dim)
    dec, _ = lm.decode_step(params, cfg, cache, toks[:, 23:],
                            jnp.asarray([23, 23], jnp.int32))
    np.testing.assert_allclose(np.asarray(dec), np.asarray(want), atol=2e-4)


# ------------------------------------------------------------- counters ----

def test_decode_moe_counts_on_hand_routes():
    # 2 expert layers, 3 slots (the last not live), 4 held experts
    routes = np.zeros((2, 3, 4), bool)
    routes[0, 0, [0, 1]] = True
    routes[0, 1, [1]] = True
    routes[0, 2, [3]] = True          # a dead slot's route counts nothing
    routes[1, 1, [0, 2, 3]] = True
    live = np.array([True, True, False])
    got = decode_moe_counts(jnp.asarray(routes), jnp.asarray(live))
    assert got.tolist() == [2 + 3, 3 + 3]


def test_decode_ticks_count_held_experts_and_assignments():
    """With the router's logits all equal, top-3 takes experts 0-2, all
    held: every live token makes 3 assignments in each of the 2 expert
    layers, and 3 held experts a layer are active.  The tick's span and
    the registry both carry the counts."""
    cfg = get_config("deepseek-v2-lite", tiny=True)
    params = lm.init_params(jax.random.key(0), cfg)
    params["layers"]["moe"]["router"] = jnp.zeros_like(
        params["layers"]["moe"]["router"])
    rng = np.random.default_rng(0)
    rec = FlightRecorder(1 << 16)
    engine = ServeEngine(cfg, EngineConfig(cache_len=48, q_chunk=16),
                         pool=WorkerPool(2, 2, seed=0),
                         policy=uniform_policy(1), params=params,
                         tracer=Tracer(rec))
    for i in range(3):
        engine.submit(Request(rid=i, prompt=rng.integers(
            1, cfg.vocab_size, 6 + 2 * i).astype(np.int32),
            max_new_tokens=5 + i))
    engine.run(max_steps=200)
    assert len(engine.completed) == 3
    ticks = [e["attrs"] for e in rec.snapshot()
             if e["type"] == "span" and e["name"] == "serve.decode"]
    assert ticks
    for t in ticks:
        assert t["moe_tokens"] == 3 * 2 * t["live"]
        assert t["moe_experts_active"] == 3 * 2
    m = engine.metrics
    assert m.moe_tokens == sum(t["moe_tokens"] for t in ticks)
    assert m.moe_experts_active == 6 * len(ticks)
    assert m.moe_tokens == 6 * m.decode_tokens


# --------------------------------------------------------- param counts ----

def test_param_counts_at_the_published_sizes():
    cfg = get_config("deepseek-v2-lite")
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256 + 2048 * 2048
    assert cfg.attention_params() == attn == 13_763_072
    held8 = dataclasses.replace(cfg, n_experts=8)
    moe_layer = (attn + 2 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1408
                 + 3 * 2048 * 2816)
    dense_layer = attn + 2 * 2048 + 3 * 2048 * 10944
    assert moe_layer == 100_405_760 and dense_layer == 81_007_104
    head = 2 * 102400 * 2048
    assert held8.param_count() == dense_layer + 26 * moe_layer + head
    whole = dense_layer + 26 * (moe_layer + 56 * 3 * 2048 * 1408) + head
    assert cfg.param_count() == whole            # 15.7B published
    # a token reaches 6 of the 64 routed experts
    assert cfg.active_param_count() == \
        whole - 26 * 58 * 3 * 2048 * 1408
    # here a token reaches 6 x 8 / 64 of the 8 held experts
    assert held8.active_param_count() == \
        held8.param_count() - 26 * 7.25 * 3 * 2048 * 1408


def test_tiny_param_count_is_the_weights():
    cfg = get_config("deepseek-v2-lite", tiny=True)
    shapes = jax.eval_shape(lambda: lm.init_params(jax.random.key(0), cfg))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    # param_count leaves out the final norm's scale
    assert cfg.param_count() == n - cfg.d_model
