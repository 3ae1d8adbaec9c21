"""Distributed-layer unit tests: sharding specs, rules, step builders."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import params as pshard
from repro.distributed.sharding import (DEFAULT_RULES, constrain,
                                        logical_to_spec, use_rules)
from repro.distributed.steps import make_train_step
from repro.launch.mesh import make_mesh
from repro.launch.shapes import SHAPES, cell_supported, input_specs
from repro.models import lm
from repro.optim import adamw_init


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(1)


def _abstract(arch):
    cfg = get_config(arch)
    return cfg, jax.eval_shape(lambda: lm.init_params(jax.random.key(0), cfg))


def test_param_specs_cover_all_leaves_and_divide(mesh):
    for arch in ("deepseek_coder_33b", "phi35_moe_42b", "recurrentgemma_2b",
                 "rwkv6_3b", "whisper_small"):
        cfg, ab = _abstract(arch)
        specs = pshard.param_specs(ab, mesh)
        flat_p = jax.tree.leaves(ab)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for leaf, spec in zip(flat_p, flat_s):
            assert len(spec) <= leaf.ndim
            for dim, part in zip(leaf.shape, tuple(spec)):
                if part is None:
                    continue
                axes = part if isinstance(part, tuple) else (part,)
                size = int(np.prod([mesh.shape[a] for a in axes]))
                assert dim % size == 0, (arch, leaf.shape, spec)


def test_zero1_strips_data_axis(mesh):
    cfg, ab = _abstract("olmo_1b")
    full = jax.tree.leaves(pshard.param_specs(ab, mesh),
                           is_leaf=lambda x: isinstance(x, P))
    z1 = jax.tree.leaves(pshard.param_specs(ab, mesh, zero1=True),
                         is_leaf=lambda x: isinstance(x, P))
    assert any("data" in tuple(s) for s in full)
    assert not any("data" in tuple(s) for s in z1)
    # model-axis TP is preserved
    assert any("model" in tuple(s) for s in z1)


def test_opt_specs_keep_master_fully_sharded(mesh):
    cfg, ab = _abstract("olmo_1b")
    opt = jax.eval_shape(lambda p: adamw_init(p, master=True), ab)
    ospec = pshard.opt_state_specs(opt, ab, mesh, zero1=True)
    assert "master" in ospec
    flat = jax.tree.leaves(ospec["master"],
                           is_leaf=lambda x: isinstance(x, P))
    assert any("data" in tuple(s) for s in flat)


def test_cache_specs_seq_sharded(mesh):
    cfg = get_config("deepseek_coder_33b")
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 128, 32768))
    specs = pshard.cache_specs(cache, cfg, mesh)
    k_spec = specs["k"]
    assert tuple(k_spec) == (None, "data", "model", None, None)


class _ProdMeshStub:
    """Production-mesh extents without needing 256 real devices."""
    shape = {"data": 16, "model": 16}
    axis_names = ("data", "model")


def test_cache_specs_fall_back_when_indivisible():
    cfg = get_config("rwkv6_3b")
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 1, 1024))
    specs = pshard.cache_specs(cache, cfg, _ProdMeshStub())
    # batch=1 cannot shard over data=16 -> replicated; heads 40 over
    # model=16 indivisible -> replicated
    assert tuple(specs["S"])[1] is None
    assert tuple(specs["S"])[2] is None
    # divisible dims keep their axes (x_tm: (L, B, D) with D=2560)
    assert tuple(specs["x_tm"])[2] == "model"


def test_cache_specs_shard_a_leading_dense_stack_as_the_rest():
    cfg = get_config("deepseek_v2_lite")
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, 32, 1024))
    specs = pshard.cache_specs(cache, cfg, _ProdMeshStub())
    for leaf in ("c_kv", "k_pe"):
        assert tuple(specs[leaf]) == (None, "data", "model", None)
        assert tuple(specs["dense_" + leaf]) == tuple(specs[leaf])


def test_param_specs_fall_back_for_indivisible_vocab():
    # granite-moe vocab 49155 does not divide model=16 -> replicated
    cfg, ab = _abstract("granite_moe_1b")
    specs = pshard.param_specs(ab, _ProdMeshStub())
    embed_spec = tuple(specs["embed"])
    assert embed_spec[0] is None           # vocab 49155 % 16 != 0
    assert embed_spec[1] == "data"         # d_model 1024 divides


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = constrain(x, ("batch", "embed"))
    assert y is x


def test_constrain_divisibility_guard(mesh):
    with use_rules(mesh):
        # 3 does not divide any axis of the debug mesh -> still legal
        x = jnp.ones((3, 5))
        y = constrain(x, ("batch", "mlp"))
        assert y.shape == x.shape


def test_logical_to_spec_respects_rules(mesh):
    with use_rules(mesh, {"seq_resid": None}):
        spec = logical_to_spec(("batch", "seq_resid", "embed"))
        assert tuple(spec)[1] is None
    with use_rules(mesh):
        spec = logical_to_spec(("batch", "seq_resid", "embed"))
        assert tuple(spec)[1] == "model"


def test_all_40_cells_are_defined():
    """The assigned matrix: 10 archs x 4 shapes, with documented skips."""
    from repro.configs import ARCHS
    n_ok = n_skip = 0
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            ok, why = cell_supported(cfg, shape)
            if ok:
                specs = input_specs(cfg, shape)
                assert specs, (arch, shape.name)
                n_ok += 1
            else:
                assert "attention" in why
                n_skip += 1
    assert n_ok == 32 and n_skip == 8


def test_train_step_with_grad_shardings_runs(mesh):
    cfg = get_config("olmo_1b", tiny=True)
    params = lm.init_params(jax.random.key(0), cfg)
    opt = adamw_init(params, master=True)
    ab = jax.eval_shape(lambda: params)
    gsh = pshard.param_shardings(ab, mesh)
    step = jax.jit(make_train_step(cfg, accum_steps=2, q_chunk=16,
                                   xent_chunk=16, grad_shardings=gsh))
    from repro.launch.shapes import make_batch
    batch = make_batch(cfg, batch=4, seq=32)
    with use_rules(mesh):
        p2, o2, m = step(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
    assert o2["step"] == 1
    # master copy tracks the bf16/fp32 params
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(o2["master"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)
