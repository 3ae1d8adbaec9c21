"""Each configuration's plain float32 reference, at a tiny width on the
CPU: its weights have the served layout, and the program run in float32
(prefill, then decoding through its cache) gives the reference's logits."""
import dataclasses
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, CONFIGS, TINY
from harness import check, counts, spec

sys.path.insert(0, str(BENCH.parent / "src"))
from repro.models import lm  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402


def _module(name):
    return spec.load_module(BENCH / "configs" / f"{name}.py", "ref_" + name)


# configurations whose module brings no counts: harness/counts.py's dense
# ones are theirs
DENSE = [c for c in CONFIGS if not any(
    hasattr(_module(c), k)
    for k in ("prefill_flops", "decode_flops", "decode_least_bytes"))]


def _setup(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    model = dict(cfg["model"], **TINY[name])
    m = dict(model, **cfg["reference"])
    return model, m, _module(name)


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_have_the_served_layout(name):
    model, m, ref = _setup(name)
    cfg = ModelConfig(**model)
    want = jax.eval_shape(lambda: lm.init_params(jax.random.key(0), cfg))
    got = jax.eval_shape(lambda: ref.init(jax.random.key(0), m))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want)


@pytest.mark.parametrize("name", CONFIGS)
def test_program_in_float32_matches_reference(name):
    model, m, ref = _setup(name)
    cfg = dataclasses.replace(ModelConfig(**model), compute_dtype="float32")
    w = ref.init(check.weight_key(2**31 + 3), m)
    toks = np.random.default_rng(0).integers(1, m["vocab_size"], 24)
    want = np.asarray(ref.forward(w, m, jnp.asarray(toks, jnp.int32)))
    p = 16
    with jax.default_matmul_precision("highest"):
        last, cache = lm.prefill(w, cfg, {"tokens": jnp.asarray(toks[None, :p])},
                                 32)
        got = [np.asarray(last[0])]
        for i in range(p, len(toks)):
            logits, cache = lm.decode_step(
                w, cfg, cache, jnp.asarray(toks[None, i:i + 1]),
                jnp.int32(i))
            got.append(np.asarray(logits[0]))
    got = np.stack(got)
    # float32 on both sides: only the order of sums differs
    np.testing.assert_allclose(got, want[p - 1:], atol=2e-4, rtol=0)


@pytest.mark.parametrize("name", CONFIGS)
def test_float8_control_moves_the_logits(name):
    _, m, ref = _setup(name)
    w = ref.init(check.weight_key(9), m)
    toks = jnp.arange(1, 33, dtype=jnp.int32)
    a = ref.forward(w, m, toks)
    b = ref.forward(w, m, toks, dtype="float8_e4m3fn")
    err = float(jnp.max(jnp.abs(a - b)))
    assert 0.05 < err < 5.0


@pytest.mark.parametrize("name", DENSE)
def test_counts_match_the_weights(name):
    _, m, ref = _setup(name)
    w = jax.eval_shape(lambda: ref.init(jax.random.key(0), m))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(w))
    embed = m["vocab_size"] * m["d_model"]
    # the least bytes of a decode tick over no cached positions: every
    # weight once in bf16, less the embedding table of an untied model
    # (only the batch's rows are read), plus one key/value row per slot
    kv_row = m["n_layers"] * 2 * m["n_kv_heads"] * (m["d_model"] //
                                                    m["n_heads"])
    untied = 0 if m["tie_embeddings"] else embed
    want = 2 * (n_params - untied + 3 * m["d_model"] + 3 * kv_row)
    assert counts.decode_least_bytes(m, [1, 1, 1]) == want
    # one decode token: 2 FLOPs per matmul weight, attention over one key
    layer = m["n_layers"] * counts.layer_matmul_params(m)
    hd = m["d_model"] // m["n_heads"]
    assert counts.decode_flops(m, 1) == 2 * layer + 4 * m["n_layers"] * \
        m["n_heads"] * hd + 2 * embed
