"""The serving weights: ``lm.serving_params`` casts each leaf the model
reads only in the compute dtype, and ``ServeEngine`` builds that copy once
and keeps no other.

Bit identity: the masked serve step and the last-index prefill give the
same logits, tokens and cache with the serving copy as with the float32
weights, for every family the engine serves.  Every leaf is first moved
off the values bfloat16 holds exactly (fresh norm gains are ones, which
it does), so a float32 leaf cast by mistake changes the logits; casting
every leaf is shown to do so.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALIASES, get_config
from repro.distributed.steps import make_prefill_step, make_serve_step
from repro.models import lm
from repro.serve import (EngineConfig, Request, ServeEngine, WorkerPool,
                         uniform_policy)
from repro.serve.engine import prefill_inputs, prefill_len
from repro.serve.snapshot import cache_batch_axes

FAMILIES = sorted(ALIASES)


def _perturbed_params(cfg, seed=0):
    """Tiny weights with every leaf moved off bfloat16's values."""
    params = lm.init_params(jax.random.key(seed), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.03 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def _kept_paths(params):
    return {jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_leaves_with_path(params)
            if path[-1].key in lm.FLOAT32_LEAVES}


def _prefill_and_step(cfg, params, plen=11):
    """One last-index prefill and one masked serve step on its cache:
    (prefill logits, step tokens, step logits, step cache) on the host."""
    rng = np.random.default_rng(3)
    offset = cfg.n_image_tokens or 0
    seq = prefill_len(cfg, plen)
    cache_len = max(offset + seq + 8, cfg.window)
    req = Request(
        rid=0, prompt=rng.integers(1, cfg.vocab_size, plen).astype(np.int32),
        max_new_tokens=4,
        frames=(rng.normal(size=(cfg.n_frames, cfg.d_model))
                .astype(np.float32) if cfg.is_encdec else None),
        image_embeds=(rng.normal(size=(cfg.n_image_tokens, cfg.d_model))
                      .astype(np.float32) if offset else None))
    prefill = jax.jit(make_prefill_step(cfg, cache_len, q_chunk=8,
                                        with_last_idx=True))
    logits0, cache = prefill(params, prefill_inputs(cfg, req, seq),
                             jnp.asarray([offset + plen - 1], jnp.int32))
    step = jax.jit(make_serve_step(cfg, cache_axes=cache_batch_axes(
        cfg, cache_len)))
    tok = jnp.argmax(logits0, -1).astype(jnp.int32)[:, None]
    nxt, logits1, cache = step(params, cache, tok,
                               jnp.asarray([offset + plen], jnp.int32),
                               jnp.asarray([True]))
    if cfg.is_moe:
        logits1, _ = logits1
    return jax.device_get((logits0, nxt, logits1, cache))


def _same(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("arch", FAMILIES)
def test_serving_weights_give_the_same_bits(arch):
    cfg = get_config(arch, tiny=True)
    params = _perturbed_params(cfg)
    served = lm.serving_params(params, cfg)
    compute = jnp.dtype(cfg.compute_dtype)
    kept = _kept_paths(params)
    for path, leaf in jax.tree_util.tree_leaves_with_path(served):
        want = (jnp.float32 if jax.tree_util.keystr(path) in kept
                else compute)
        assert leaf.dtype == want, jax.tree_util.keystr(path)
    want = _prefill_and_step(cfg, params)
    assert _same(_prefill_and_step(cfg, served), want)
    if kept:
        # the perturbation bites: a float32 leaf read in bfloat16 shows
        every = jax.tree.map(lambda x: x.astype(compute), params)
        got = _prefill_and_step(cfg, every)
        assert not np.array_equal(got[0], want[0])
        assert not np.array_equal(got[2], want[2])


def test_serving_params_of_compute_dtype_weights_is_the_identity():
    cfg = dataclasses.replace(get_config("deepseek-v2-lite", tiny=True),
                              param_dtype="bfloat16")
    params = lm.init_params(jax.random.key(0), cfg)
    served = lm.serving_params(params, cfg)
    assert all(a is b for a, b in zip(jax.tree.leaves(served),
                                      jax.tree.leaves(params)))


def _engine(cfg, params):
    return ServeEngine(cfg, EngineConfig(cache_len=32, q_chunk=8),
                       pool=WorkerPool(1, 2, seed=0),
                       policy=uniform_policy(1), params=params)


@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "recurrentgemma-2b",
                                  "whisper-small"])
def test_engine_takes_the_weights_it_is_given(arch):
    """The cast leaves take the compute dtype on the given leaf's sharding
    and the given leaf is released; the float32 leaves are the given ones;
    ``serve_weight_bytes`` reads the tree's bytes by dtype."""
    cfg = get_config(arch, tiny=True)
    params = lm.init_params(jax.random.key(0), cfg)
    given = dict(jax.tree_util.tree_leaves_with_path(params))
    kept = _kept_paths(params)
    assert kept
    e = _engine(cfg, params)
    compute = jnp.dtype(cfg.compute_dtype)
    nbytes = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(e.params):
        old = given[path]
        if jax.tree_util.keystr(path) in kept:
            assert leaf is old and leaf.dtype == jnp.float32
            assert not old.is_deleted()
        else:
            assert leaf.dtype == compute and old.is_deleted()
            assert leaf.sharding == old.sharding
        nbytes[leaf.dtype.name] = nbytes.get(leaf.dtype.name, 0) + leaf.nbytes
    assert set(nbytes) == {"float32", compute.name}
    gauge = e.metrics.registry.get("serve_weight_bytes")
    assert {k: gauge.value(dtype=k) for k in nbytes} == nbytes
    released = next(v for v in given.values() if v.is_deleted())
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(released)


def test_engine_casts_and_releases_nothing_of_compute_dtype_weights():
    cfg = dataclasses.replace(get_config("deepseek-v2-lite", tiny=True),
                              param_dtype="bfloat16")
    params = lm.init_params(jax.random.key(0), cfg)
    e = _engine(cfg, params)
    for got, leaf in zip(jax.tree.leaves(e.params), jax.tree.leaves(params)):
        assert got is leaf and not leaf.is_deleted()
    total = sum(x.nbytes for x in jax.tree.leaves(params))
    gauge = e.metrics.registry.get("serve_weight_bytes")
    assert gauge.value(dtype="bfloat16") == total
    assert gauge.value(dtype="float32") == 0


def test_engine_weights_keep_their_mesh_sharding():
    """On a (1, 4) mesh, weights placed by the program's parameter
    shardings keep them through the cast, and the engine still serves.  In
    a child process of its own, since the device count is fixed when JAX
    starts."""
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.configs import get_config
        from repro.distributed import params as pshard
        from repro.distributed.sharding import use_rules
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        from repro.serve import (EngineConfig, Request, ServeEngine,
                                 WorkerPool, uniform_policy)
        cfg = get_config("deepseek-v2-lite", tiny=True)
        mesh = make_mesh(4)
        init = lambda: lm.init_params(jax.random.key(0), cfg)
        psh = pshard.param_shardings(jax.eval_shape(init), mesh, zero1=True)
        params = jax.jit(init, out_shardings=psh)()
        given = jax.tree.leaves(params)
        with use_rules(mesh):
            e = ServeEngine(cfg, EngineConfig(cache_len=32, q_chunk=8),
                            pool=WorkerPool(2, 2, seed=0),
                            policy=uniform_policy(1), params=params)
            split = 0
            for old, new in zip(given, jax.tree.leaves(e.params)):
                assert new.sharding == old.sharding, (new.sharding,
                                                      old.sharding)
                assert (new.dtype == np.float32) != old.is_deleted()
                split += len(new.sharding.device_set) == 4 and \\
                    new.addressable_shards[0].data.size < new.size
            assert split > 0
            rng = np.random.default_rng(0)
            for i in range(3):
                e.submit(Request(rid=i, prompt=rng.integers(
                    1, cfg.vocab_size, 6 + i).astype(np.int32),
                    max_new_tokens=4))
            e.run(max_steps=100)
            assert len(e.completed) == 3
        print("ok")
        """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code],
                         cwd=Path(__file__).resolve().parents[1], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert out.stdout.strip().endswith("ok")
