"""Serving weights against float32 weights, on the chip at a benchmark
configuration's size.

A last-index prefill of a few prompts fills their slots of the decode
cache, then one masked serve step runs over every slot (the filled ones
live).  Both run first on the float32 weights, then on the serving copy
that ``ServeEngine`` makes (``take_serving_weights``, which releases the
float32 leaves it casts), with the same prompts, cache and tokens; the
weights are first moved off bfloat16's values.  The
two must agree bit for bit: prefill logits, the cache the prefills
write, and the step's tokens, logits and cache.  Prints one JSON line.

    python benchmarks/serving_weights_parity.py \
        --config bench/configs/deepseek-coder-33b.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.distributed.steps import make_prefill_step, make_serve_step  # noqa: E402
from repro.launch.mesh import enable_compile_cache  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.serve.engine import (prefill_len,  # noqa: E402
                                take_serving_weights)
from repro.serve.snapshot import cache_batch_axes, slot_set  # noqa: E402


def _differences(a, b) -> list[dict]:
    """Leaves of two host trees that differ: where, and by how much."""
    out = []
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        if not np.array_equal(x, y):
            d = np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64))
            out.append({"leaf": jax.tree_util.keystr(path),
                        "differ": int((d > 0).sum()),
                        "max_abs": float(d.max())})
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--prompts", default="1000,700,300",
                    help="prompt lengths, one slot each")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    conf = json.loads(open(args.config).read())
    cfg = ModelConfig(**conf["model"])
    dep = conf["deployment"]
    slots = dep["workers"] * dep["slots_per_worker"]
    cache_len = dep["cache_len"]
    lens = [int(n) for n in args.prompts.split(",")]
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    axes = cache_batch_axes(cfg, cache_len)
    step = jax.jit(make_serve_step(cfg, cache_axes=axes), donate_argnums=(1,))
    prefills = {}

    def fill(params):
        """Prefill each prompt into its slot: (logits, cache on the host)."""
        cache = lm.init_cache(cfg, slots, cache_len)
        logits = []
        for sid, prompt in enumerate(prompts):
            seq = prefill_len(cfg, len(prompt))
            fn = prefills.setdefault(seq, jax.jit(make_prefill_step(
                cfg, cache_len, q_chunk=64, with_last_idx=True)))
            tokens = np.zeros((1, seq), np.int32)
            tokens[0, :len(prompt)] = prompt
            out, row1 = fn(params, {"tokens": jnp.asarray(tokens)},
                           jnp.asarray([len(prompt) - 1], jnp.int32))
            row = jax.tree.map(lambda l, a: jnp.squeeze(l, a), row1, axes)
            cache = slot_set(cache, axes, sid, row)
            logits.append(np.asarray(out[0]))
        return np.stack(logits), jax.device_get(cache)

    def decode(params, host_cache, tokens):
        live = np.arange(slots) < len(prompts)
        pos = np.where(live, np.pad(lens, (0, slots - len(lens))), 0)
        cache = jax.device_put(host_cache)
        nxt, logits, cache = step(params, cache,
                                  jnp.asarray(tokens[:, None]),
                                  jnp.asarray(pos, jnp.int32),
                                  jnp.asarray(live))
        return jax.device_get((nxt, logits, cache))

    key = jax.random.key(args.seed)
    params = jax.jit(lambda k: lm.init_params(k, cfg))(key)
    # move the weights off bfloat16's values (fresh norm gains are ones),
    # so that a leaf read in float32 but cast by mistake would show
    params = jax.tree.map(lambda x: x * (1 + 0.01 / 3), params)
    logits32, cache32 = fill(params)
    tokens = np.zeros((slots,), np.int32)
    tokens[:len(prompts)] = logits32.argmax(-1)
    out32 = decode(params, cache32, tokens)
    t0 = time.perf_counter()
    params = take_serving_weights(params, cfg)
    cast_s = time.perf_counter() - t0
    logits, cache = fill(params)
    out = decode(params, cache32, tokens)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    diff = {"prefill_logits": _differences(logits32, logits),
            "prefill_cache": _differences(cache32, cache),
            "step": _differences(out32, out)}
    print(json.dumps({
        "config": cfg.name, "device": dev.device_kind, "slots": slots,
        "cache_len": cache_len, "prompts": lens, "cast_s": cast_s,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bit_identical": not any(diff.values()), "differences": diff}))


if __name__ == "__main__":
    main()
