"""The control must fail the correctness limit: the reference computed one
precision step below the configuration's bfloat16 (every matmul operand
rounded to float8_e4m3fn) puts first, at some position, a token whose
float32 gap exceeds the limit.  At the largest size a CPU test holds
(width 512, 4 layers, vocabulary 4096, 256 positions), on three seeds;
the same reading at the cells' own sizes on the chip is in PERF.md."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH
from harness import check, spec

LIMIT = json.loads((BENCH / "cells" /
                    "deepseek-coder-33b.chat-saturated.json"
                    ).read_text())["check"]["max_logit_gap"]
SIZE = {"n_layers": 4, "d_model": 512, "n_heads": 8, "d_ff": 1024,
        "vocab_size": 4096}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name,kv", [("deepseek-coder-33b", 2)])
def test_float8_control_exceeds_the_limit(name, kv, seed):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    m = dict(cfg["model"], **cfg["reference"], **SIZE, n_kv_heads=kv)
    ref = spec.load_module(BENCH / "configs" / f"{name}.py", "ref_" + name)
    w = ref.init(check.weight_key(seed), m)
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        1, m["vocab_size"], 256), jnp.int32)
    fwd = jax.jit(lambda w, t, dt: ref.forward(w, m, t, dtype=dt),
                  static_argnums=2)
    f32 = np.asarray(fwd(w, toks, "float32"))
    f8 = np.asarray(fwd(w, toks, "float8_e4m3fn"))
    control = check.gaps(f32, f8.argmax(-1)).max()
    assert control > LIMIT
