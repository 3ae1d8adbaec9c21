"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
program that does not fit the chip's memory, a kernel slice not aligned to
the tiling.  These compiles guard the serve step, prefill, the Pallas
distance kernel and the snapshot's chunk programs on a KV cache sharded
over four chips, at the sizes ``chip_smoke.py`` runs, at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.configs import get_config
from repro.distributed import params as pshard
from repro.distributed.steps import make_prefill_step, make_serve_step
from repro.kernels.pairwise_affinity import ops as pa_ops
from repro.models import lm
from repro.serve.snapshot import SlotLayout, cache_batch_axes

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def olmo(one_chip):
    cfg = get_config("olmo-1b")
    params = jax.eval_shape(lambda: lm.init_params(jax.random.key(0), cfg))
    return cfg, _on(one_chip, params)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, f"{used / 2 ** 30:.2f} GiB > 16 GiB"
    return used


def _serve_step(cfg, params, one_chip, slots, cache_len):
    """The engine's masked serve step compiled for one described chip."""
    cache = _on(one_chip, jax.eval_shape(
        lambda: lm.init_cache(cfg, slots, cache_len)))
    step = jax.jit(make_serve_step(cfg, cache_axes=cache_batch_axes(
        cfg, cache_len)), donate_argnums=(1,))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return step.lower(params, cache, sds((slots, 1), jnp.int32),
                      sds((slots,), jnp.int32),
                      sds((slots,), jnp.bool_)).compile()


def test_olmo_masked_serve_step_fits_one_chip(olmo, one_chip,
                                              no_compile_cache):
    cfg, params = olmo
    _fits(_serve_step(cfg, params, one_chip, 8, 2048))


def test_olmo_prefill_fits_one_chip(olmo, one_chip, no_compile_cache):
    cfg, params = olmo
    bucket, cache_len = 512, 2048
    prefill = jax.jit(make_prefill_step(cfg, cache_len, q_chunk=64,
                                        with_last_idx=True))
    tokens = jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=one_chip)
    last = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    compiled = prefill.lower(params, {"tokens": tokens}, last).compile()
    _fits(compiled)


@pytest.fixture(scope="module")
def v2_lite(one_chip):
    """DeepSeek-V2-Lite as the benchmark runs it: 12 layers, 8 of 64
    routed experts held (``bench/configs/deepseek-v2-lite.json``)."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), n_layers=12,
                              n_experts=8)
    params = jax.eval_shape(lambda: lm.init_params(jax.random.key(0), cfg))
    return cfg, _on(one_chip, params)


def test_v2_lite_masked_serve_step_fits_one_chip(v2_lite, one_chip,
                                                 no_compile_cache):
    """32 slots of 9216 latent rows beside the float32 weights: the
    deepest stage the compiler accepts (13 layers are refused)."""
    cfg, params = v2_lite
    _fits(_serve_step(cfg, params, one_chip, 32, 9216))


@pytest.fixture(scope="module")
def coder(one_chip):
    """deepseek-coder-33b as the benchmark runs it: 4 layers at published
    width (``bench/configs/deepseek-coder-33b.json``)."""
    cfg = dataclasses.replace(get_config("deepseek-coder-33b"), n_layers=4)
    params = jax.eval_shape(lambda: lm.init_params(jax.random.key(0), cfg))
    return cfg, _on(one_chip, params)


def _bytes_accessed(compiled) -> float:
    cost = compiled.cost_analysis()
    return (cost[0] if isinstance(cost, list) else cost)["bytes accessed"]


@pytest.mark.parametrize("model, slots, cache_len, gib, share", [
    ("coder", 12, 4608, 8, 0.5),      # float32 weights: 15.53 GiB
    ("v2_lite", 32, 9216, 12, 0.75),  # float32 weights: 15.88 GiB
])
def test_serving_weights_shrink_the_serve_step(request, one_chip,
                                               no_compile_cache, model,
                                               slots, cache_len, gib, share):
    """Each cell's masked serve step on the engine's serving weights: no
    copy of the weights in the compute dtype made on every call, so the
    program fits in ``gib`` GiB and moves under ``share`` of the bytes it
    moves on float32 weights."""
    cfg, params = request.getfixturevalue(model)
    served = _on(one_chip, jax.eval_shape(
        lambda p: lm.serving_params(p, cfg), params))
    f32 = _serve_step(cfg, params, one_chip, slots, cache_len)
    bf16 = _serve_step(cfg, served, one_chip, slots, cache_len)
    assert _fits(bf16) < gib * 2 ** 30
    assert _bytes_accessed(bf16) < share * _bytes_accessed(f32)


def test_v2_lite_longest_prefill_fits_beside_the_cache(v2_lite, one_chip,
                                                       no_compile_cache):
    cfg, params = v2_lite
    prefill = jax.jit(make_prefill_step(cfg, 9216, q_chunk=64,
                                        with_last_idx=True))
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    last = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    compiled = prefill.lower(params, {"tokens": tokens}, last).compile()
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 32, 9216))
    resident = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert _fits(compiled) + resident < V5E_HBM_BYTES


def test_pairwise_distance_kernel_compiles_for_the_chip(one_chip,
                                                        no_compile_cache):
    # the paper's largest workflow: 700 tasks x 10 task features
    pts = jax.ShapeDtypeStruct((700, 10), jnp.float32, sharding=one_chip)
    compiled = jax.jit(pa_ops.pairwise_distance).lower(pts).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_snapshot_chunk_programs_stay_inside_kv_seq_shards(topo,
                                                           no_compile_cache):
    """On a ``(1, 4)`` mesh the KV cache is split along its sequence axis
    (``kv_seq``).  Reading or writing one chunk of one slot moves that
    chunk only: no collective larger than a chunk from each chip, and no
    scratch buffer of the cache's size."""
    cfg = get_config("olmo-1b")
    slots, cache_len = 8, 576       # chip_smoke.py --chips 4
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    shapes = jax.eval_shape(lambda: lm.init_cache(cfg, slots, cache_len))
    cache = jax.tree.map(
        lambda a, p: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, p)),
        shapes, pshard.cache_specs(shapes, cfg, mesh))
    assert cache["k"].sharding.spec[2] == "model"
    lay = SlotLayout(cfg, cache_len,
                     jax.tree.map(lambda a: a.sharding, cache))
    assert (cache_len // 4) % lay.chunk == 0
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    rows = jax.eval_shape(lay.slot_read_rows, cache, i32, i32)
    chunk_elems = max(math.prod(r.shape) for r in rows)
    shard_bytes = cache["k"].size * cache["k"].dtype.itemsize // 4
    read = jax.jit(lay.slot_read_rows).lower(cache, i32, i32).compile()
    write = jax.jit(lay.slot_write_rows, donate_argnums=(0,)).lower(
        cache, i32, i32, [jax.ShapeDtypeStruct(r.shape, r.dtype)
                          for r in rows]).compile()
    for compiled in (read, write):
        for dims in re.findall(
                r"= \w+\[([\d,]*)\][^ ]* (?:all-gather|all-reduce|"
                r"collective-permute|all-to-all)(?:-start)?\(",
                compiled.as_text()):
            n = math.prod(int(d) for d in dims.split(",") if d)
            assert n <= 4 * chunk_elems, dims
        assert compiled.memory_analysis().temp_size_in_bytes < shard_bytes
