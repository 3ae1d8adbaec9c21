"""Validation of the roofline analysis machinery.

1. XLA's cost_analysis counts while-loop bodies once (the reason we use an
   analytic FLOP model) -- demonstrated directly.
2. The analytic FLOP model matches cost_analysis on *unrolled* (scan-free)
   forwards within tolerance.
3. The HLO collective parser scales loop-nested collectives by trip count.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import flops as F
from repro.analysis import hlo as H
from repro.configs import get_config
from repro.launch.shapes import Shape
from repro.models import lm
from repro.models.config import ModelConfig


def test_cost_analysis_counts_scan_bodies_once():
    def scan_fn(x, w):
        def body(c, wi):
            return c @ wi, None
        out, _ = jax.lax.scan(body, x, w)
        return out

    def unroll_fn(x, w):
        for i in range(8):
            x = x @ w[i]
        return x

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    fs = jax.jit(scan_fn).lower(x, w).compile().cost_analysis()["flops"]
    fu = jax.jit(unroll_fn).lower(x, w).compile().cost_analysis()["flops"]
    assert fu == pytest.approx(8 * fs, rel=0.01)


def _unrolled_last_logits(params, cfg, batch):
    """Scan-free forward (prefill semantics: last-token logits)."""
    dtype = jnp.float32
    x = params["embed"].astype(dtype)[batch["tokens"]]
    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    L = jax.tree.leaves(params["layers"])[0].shape[0]
    for i in range(L):
        layer = jax.tree.map(lambda p: p[i], params["layers"])
        x, _ = lm._dense_block(layer, x, cfg, pos, q_chunk=x.shape[1])
    x = lm.apply_norm(cfg, params["final_norm"], x)
    w = lm.output_weights(params, cfg, dtype)
    return (x[:, -1] @ w).astype(jnp.float32)


@pytest.mark.parametrize("arch,rel", [("olmo_1b", 0.35),
                                      ("phi35_moe_42b", 0.45)])
def test_analytic_flops_match_unrolled_hlo(arch, rel):
    cfg = dataclasses.replace(
        get_config(arch, tiny=True), n_layers=3, d_model=256,
        n_heads=4, n_kv_heads=2, d_ff=1024, vocab_size=2048,
        compute_dtype="float32", remat=False)
    b, s = 2, 256
    params = jax.eval_shape(lambda: lm.init_params(jax.random.key(0), cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    compiled = jax.jit(
        lambda p, bt: _unrolled_last_logits(p, cfg, bt)).lower(
        params, batch).compile()
    hlo_flops = compiled.cost_analysis()["flops"]
    shape = Shape("prefill_test", "prefill", s, b)
    analytic = F.cell_flops(cfg, shape).flops
    assert analytic == pytest.approx(hlo_flops, rel=rel), \
        f"analytic {analytic:.3g} vs HLO {hlo_flops:.3g}"


def test_model_flops_ratio_sane():
    cfg = get_config("deepseek_coder_33b")
    from repro.launch.shapes import SHAPES
    cost = F.cell_flops(cfg, SHAPES["train_4k"])
    # 6ND is a lower bound on compiled work: attention + remat push above it
    assert cost.flops > cost.model_flops
    assert cost.model_flops / cost.flops > 0.3


SYNTH_HLO = """
HloModule test

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%body.1 (p: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %p = (s32[], f32[128,256]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[128,256] get-tuple-element(%p), index=1
  %ag = f32[128,256] all-gather(%x), replica_groups={}, dimensions={0}
  %ar = f32[128,256] all-reduce(%ag), to_apply=%add
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[128,256]) tuple(%ip, %ar)
}

%cond.1 (p: (s32[], f32[128,256])) -> pred[] {
  %p = (s32[], f32[128,256]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(24)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[128,256]) -> f32[128,256] {
  %x = f32[128,256] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[128,256]) tuple(%zero, %x)
  %w = (s32[], f32[128,256]) while(%init), condition=%cond.1, body=%body.1
  %y = f32[128,256] get-tuple-element(%w), index=1
  ROOT %out = f32[128,256] all-gather(%y), replica_groups={}, dimensions={0}
}
"""


def test_hlo_collective_parser_scales_by_trip_count():
    totals = H.collective_totals(SYNTH_HLO)
    assert totals["scaled"]
    tensor = 128 * 256 * 4
    # all-gather: 24 in-loop + 1 at top level; all-reduce: 24 in-loop
    assert totals["bytes"]["all-gather"] == 25 * tensor
    assert totals["bytes"]["all-reduce"] == 24 * tensor
    assert totals["counts"]["all-gather"] == 25
    assert H.link_bytes(totals) == pytest.approx(
        25 * tensor + 2.0 * 24 * tensor)


def test_hlo_parser_on_real_dryrun_artifact():
    import glob
    import os
    files = glob.glob(os.path.join(os.path.dirname(__file__), "..",
                                   "benchmarks", "out", "dryrun",
                                   "*train_4k__single.hlo.gz"))
    if not files:
        pytest.skip("no dry-run artifacts present")
    totals = H.collective_totals(H.load_hlo(files[0]))
    assert totals["scaled"]
    assert sum(totals["bytes"].values()) > 0
    # scaled totals must exceed a flat (body-once) grep
    flat = H.parse_computations(H.load_hlo(files[0]))[0]
    flat_sum = sum(sum(c.coll_bytes.values()) for c in flat.values())
    assert sum(totals["bytes"].values()) >= flat_sum
