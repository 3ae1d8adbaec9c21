"""Chip smoke test: the serving launcher's main path on a TPU.

A smoke test, not a benchmark: it shows the system starts and answers
correctly on the chip, and its times include compilation.  Everything runs
in this one process (a chip belongs to one process at a time).

Default phases, on one chip:

* **serve** — olmo-1b at its published widths with seeded random weights,
  through ``repro.launch.serve.continuous_main`` (``ServeEngine``, CRCH
  replication, ``--env normal`` worker failures, decode-snapshot restores):
  every request completes; every logits row the engine computed agrees with
  the batch=1 static reference (``repro.serve.reference``) fed the same
  tokens, and every delivered token is that reference's greedy choice or
  within a stated near-tie of it; one prompt's prefill logits agree with
  the same model run in float32 on the host's CPU backend.  The same run
  in float32 with full-precision matmuls, where no near-tie flips, is
  token-exact against ``greedy_reference``.
* **scheduler** — the CRCH plan on a 700-task Montage workflow (the paper's
  largest size) with the Pallas pairwise-distance kernel compiled for the
  chip: its distances agree with the jnp backend's and the replication
  counts are identical.

``--chips 4`` runs only the serve phase, on a ``(1, 4)`` mesh (weights in
the TP layout, KV cache sharded along ``kv_seq``), and compares its prefill
logits with the same prompts on a ``(1, 1)`` mesh on device 0 in place of
the host float32 ones.

    python chip_smoke.py [--chips 4]

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a
TPU the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the float32 reference runs on the host's CPU backend: keep it available
# when the platform list is pinned (the default device stays the first one)
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import CloudEnvironment, CRCHConfig, generate_workflow  # noqa: E402
from repro.core import pairwise_distances, plan  # noqa: E402
from repro.distributed import params as pshard  # noqa: E402
from repro.distributed.sharding import use_rules  # noqa: E402
from repro.distributed.steps import make_prefill_step  # noqa: E402
from repro.kernels.pairwise_affinity import ops as pa_ops  # noqa: E402
from repro.launch import serve as launch_serve  # noqa: E402
from repro.launch.mesh import enable_compile_cache, make_mesh  # noqa: E402
from repro.serve import (prefill_inputs, prefill_len,  # noqa: E402
                         reference_logits)

# 8 requests of 89-490 prompt tokens (drawn from 64-512) and 32 or 64 new
# tokens on 2 workers x 4 slots.  The failure schedule is drawn on the host
# from --seed, so seed 5 gives the same worker failures and snapshot
# restores at every model size.
SERVE_ARGV = ("--arch", "olmo-1b", "--requests", "8", "--prompt-len", "512",
              "--min-prompt-len", "64", "--new-tokens", "32", "--workers",
              "2", "--slots-per-worker", "4", "--policy", "crch", "--env",
              "normal", "--seed", "5")

# Logit bounds: (largest |difference|, RMS difference as a share of the
# reference logits' RMS).
#
# bf16 activations against a float32 forward pass: bf16 keeps 8 significant
# bits and the 16-layer residual stream accumulates its rounding.  The same
# comparison on CPU at widths 256-1024 gave 0.06 and 1.5%, on one v5e chip
# 0.0499 and 1.35%; the bounds leave four times the CPU reading.
HOST_F32_BOUND = (0.25, 0.05)
# bf16 against bf16 on the same devices, differing only in shapes (prompt
# padded to its bucket, a batch of 8 slots against batch 1) or in how the
# sums are split (a (1, 4) mesh against one chip), hence in the order XLA
# sums in.  On v5e, 4 chips against 1 measured 0.0625 and 1.43% on prefill
# logits, the engine against the reference 0.0616 and 1.23% on one chip
# and 0.0688 and 1.54% on four; the bounds leave about twice that.
SAME_DTYPE_BOUND = (0.125, 0.03)
# A delivered token other than the reference's argmax is a bf16 near-tie
# when the reference ranks it at most this far below its best.  Two logit
# rows within SAME_DTYPE_BOUND's largest difference d of each other can
# disagree on the argmax only where the reference's gap is below 2 d.
TIE_MARGIN = 2 * SAME_DTYPE_BOUND[0]

# Distances, Pallas kernel against the jnp backend (both float32 at
# HIGHEST precision): the ||x||^2 + ||y||^2 - 2<x,y> expansion cancels for
# near points, as in tests/test_kernels.py.
DIST_ATOL, DIST_RTOL = 3e-3, 1e-3

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# -- serve --------------------------------------------------------------------

def serve_phase(cfg, mesh) -> tuple[launch_serve.ServeRun, dict]:
    """One run of the serving launcher's engine path on ``mesh``, checked
    against the static reference."""
    args = launch_serve.build_parser().parse_args(
        [*SERVE_ARGV, "--chips", str(mesh.devices.size)])
    run = launch_serve.continuous_main(cfg, mesh, args, record_logits=True)
    s = run.summary
    check(int(s["completed"]) == len(run.requests),
          f"completed {int(s['completed'])}/{len(run.requests)} requests")
    check(s["failures"] > 0 and s["restores"] > 0,
          f"no failure or snapshot restore happened (failures "
          f"{int(s['failures'])}, restores {int(s['restores'])})")
    return run, engine_vs_reference(cfg, run, mesh)


def float32_phase(cfg, mesh) -> launch_serve.ServeRun:
    """The serve run again with float32 activations and full-precision
    matmuls, where rounding no longer breaks greedy near-ties: the engine's
    tokens must equal ``greedy_reference``'s token for token."""
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")
    args = launch_serve.build_parser().parse_args(
        [*SERVE_ARGV, "--chips", str(mesh.devices.size), "--verify-static"])
    with jax.default_matmul_precision("highest"):
        run = launch_serve.continuous_main(cfg32, mesh, args)
    check(int(run.summary["completed"]) == len(run.requests),
          f"float32: completed {int(run.summary['completed'])}/"
          f"{len(run.requests)} requests")
    mismatched = [r.rid for r in run.requests
                  if run.engine.output(r.rid) != run.reference[r.rid]]
    check(not mismatched, f"float32: tokens differ from greedy_reference "
                          f"for rids {mismatched}")
    return run


def engine_vs_reference(cfg, run: launch_serve.ServeRun, mesh) -> dict:
    """Every logits row the engine computed, by any copy of a request,
    against the static reference fed the tokens the engine delivered; and
    every delivered token against that reference's argmax.  With random
    weights a deep model's greedy tokens may barely vary, so the logits
    carry the check: a stale or misplaced cache row that keeps the argmax
    still moves them."""
    delivered = {r.rid: run.engine.output(r.rid) for r in run.requests}
    with use_rules(mesh):
        ref = reference_logits(run.params, cfg, run.requests, run.cache_len,
                               delivered, q_chunk=64)
    worst = {"max_abs": 0.0, "rel_rms": 0.0}
    for rid, k, row in run.engine.logit_log:
        cmp = compare_logits(row, ref[rid][k], SAME_DTYPE_BOUND,
                             f"rid {rid} token {k}: engine vs reference")
        worst = {key: max(worst[key], cmp[key]) for key in worst}
    exact, ties = 0, []
    for rid, toks in delivered.items():
        rows, toks = ref[rid], np.asarray(toks)
        gap = rows.max(-1) - rows[np.arange(len(toks)), toks]
        k = int(gap.argmax())
        check(gap[k] <= TIE_MARGIN,
              f"rid {rid} token {k}: the engine delivered {toks[k]}, which "
              f"the reference ranks {gap[k]} below its best "
              f"{rows[k].argmax()} (near-tie bound {TIE_MARGIN})")
        flips = np.flatnonzero(rows.argmax(-1) != toks)
        if flips.size:
            ties.append((rid, int(flips[0]), float(gap[flips[0]])))
        else:
            exact += 1
    return {"rows": len(run.engine.logit_log), **worst, "exact": exact,
            "ties": ties, "distinct_tokens":
                len({t for toks in delivered.values() for t in toks})}


def prefill_logits(cfg, mesh, params, req, cache_len: int) -> np.ndarray:
    """Last-prompt-token logits of the engine's own prefill program."""
    seq = prefill_len(cfg, req.prompt_len)
    with use_rules(mesh):
        fn = jax.jit(make_prefill_step(cfg, cache_len, q_chunk=min(64, seq),
                                       with_last_idx=True))
        logits, _ = fn(params, prefill_inputs(cfg, req, seq),
                       jnp.asarray([req.prompt_len - 1], jnp.int32))
    return np.asarray(logits[0])


def host_f32_logits(cfg, params, req) -> np.ndarray:
    """The same model in float32 on the host CPU, exact prompt length."""
    cpu = jax.devices("cpu")[0]
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")
    host = jax.device_put(jax.device_get(params), cpu)
    tokens = jax.device_put(np.asarray(req.prompt, np.int32)[None], cpu)
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        fn = jax.jit(make_prefill_step(cfg32, req.prompt_len,
                                       q_chunk=min(64, req.prompt_len)))
        logits, _ = fn(host, {"tokens": tokens})
    return np.asarray(logits[0])


def compare_logits(got: np.ndarray, want: np.ndarray, bound, what: str
                   ) -> dict:
    diff = np.abs(got.astype(np.float64) - want.astype(np.float64))
    max_abs = float(diff.max())
    rel_rms = float(np.sqrt(np.mean(diff ** 2) /
                            np.mean(want.astype(np.float64) ** 2)))
    check(np.isfinite(got).all(), f"{what}: non-finite logits")
    check(max_abs <= bound[0] and rel_rms <= bound[1],
          f"{what}: max |diff| {max_abs} (bound {bound[0]}), RMS diff "
          f"{rel_rms} of the RMS (bound {bound[1]})")
    return {"max_abs": max_abs, "rel_rms": rel_rms}


def serve_vs_host(cfg, run: launch_serve.ServeRun, mesh) -> dict:
    """The shortest prompt's prefill logits on the mesh vs host float32."""
    req = min(run.requests, key=lambda r: r.prompt_len)
    got = prefill_logits(cfg, mesh, run.params, req, run.cache_len)
    want = host_f32_logits(cfg, run.params, req)
    return {"prompt_len": req.prompt_len,
            **compare_logits(got, want, HOST_F32_BOUND,
                             "prefill vs host float32")}


def sharded_vs_one_chip(cfg, run: launch_serve.ServeRun, mesh) -> dict:
    """Check the weights and cache span the mesh, then compare every
    prompt's prefill logits with a (1, 1) mesh on device 0."""
    n = mesh.devices.size
    for name, leaf in (("weight layers.attn.wq",
                        run.params["layers"]["attn"]["wq"]),
                       ("cache k", run.engine.cache["k"])):
        shard = leaf.addressable_shards[0].data.shape
        check(len(leaf.sharding.device_set) == n,
              f"{name} spans {len(leaf.sharding.device_set)}/{n} devices")
        check(np.prod(shard) * n == np.prod(leaf.shape),
              f"{name} {leaf.shape} is not split {n} ways (shard {shard})")
        print(f"{name} {leaf.shape}: {len(leaf.sharding.device_set)} "
              f"devices, shard {shard}")
    one = make_mesh(1)
    params1 = jax.device_put(run.params, pshard.param_shardings(
        jax.eval_shape(lambda: run.params), one, zero1=True))
    worst = {"max_abs": 0.0, "rel_rms": 0.0}
    for req in run.requests:
        got = prefill_logits(cfg, mesh, run.params, req, run.cache_len)
        want = prefill_logits(cfg, one, params1, req, run.cache_len)
        cmp = compare_logits(got, want, SAME_DTYPE_BOUND,
                             f"rid {req.rid}: {n} chips vs 1")
        worst = {k: max(worst[k], cmp[k]) for k in worst}
    return worst


# -- scheduler ----------------------------------------------------------------

def scheduler_phase(n_tasks: int = 700, *, interpret: bool = False) -> dict:
    """CRCH plan with the Pallas distance kernel against the jnp backend."""
    wf = generate_workflow("montage", n_tasks, seed=0)
    env = CloudEnvironment(wf, 20, seed=1)
    ref = plan(wf, env, CRCHConfig(backend="jnp"))
    got = plan(wf, env, CRCHConfig(backend="pallas", interpret=interpret))
    check(np.array_equal(got.rep_counts, ref.rep_counts),
          "replication counts differ between the pallas and jnp backends")
    pts = ref.pca.projected
    d_ref = pairwise_distances(pts, backend="jnp")
    d_got = pairwise_distances(pts, backend="pallas", interpret=interpret)
    max_abs = float(np.abs(d_got - d_ref).max())
    check(np.allclose(d_got, d_ref, atol=DIST_ATOL, rtol=DIST_RTOL),
          f"pallas distances differ from jnp by up to {max_abs}")
    hlo = jax.jit(lambda x: pa_ops.pairwise_distance(
        x, interpret=interpret)).lower(jnp.asarray(pts, jnp.float32)).as_text()
    compiled_kernel = "tpu_custom_call" in hlo
    check(interpret or compiled_kernel,
          "the pallas backend did not lower to a compiled TPU kernel")
    return {"tasks": len(wf.tasks), "features": pts.shape[1],
            "max_abs": max_abs, "compiled_kernel": compiled_kernel,
            "rep_histogram": np.bincount(got.rep_counts).tolist()}


# -- entry point --------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the serve phase, on a (1, 4) mesh")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's default "
                         f"platform here is {dev.platform!r}")
    cache_dir = enable_compile_cache()
    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    print(f"chip smoke test (not a benchmark): {dev.device_kind} x "
          f"{len(jax.devices())}, compile cache {cache_dir}")
    cfg = get_config("olmo-1b")
    mesh = make_mesh(args.chips)
    run, par = serve_phase(cfg, mesh)
    s = run.summary
    print(f"smoke serve: {cfg.name} {args.chips} chip(s), "
          f"{int(s['completed'])}/{len(run.requests)} completed, "
          f"{int(s['failures'])} failures, {int(s['restores'])} snapshot "
          f"restores, {int(s['snapshots'])} snapshots of which "
          f"{int(run.engine.metrics.snapshot_deltas)} extended a lineage; "
          f"wall {run.wall_s:.3f} s (compiles and logit copies "
          f"included), {run.engine.metrics.decode_tokens} decode tokens = "
          f"{run.engine.metrics.decode_tokens / run.wall_s:.1f} tok/s")
    print(f"smoke parity: {par['rows']} engine logit rows vs the static "
          f"reference: max |diff| {par['max_abs']:.5f}, RMS diff "
          f"{par['rel_rms']:.5f} of the RMS; {par['exact']}/"
          f"{len(run.requests)} requests token-exact, near-ties (rid, "
          f"token, gap) {par['ties']}; {par['distinct_tokens']} distinct "
          f"tokens delivered")
    if args.chips == 1:
        cmp = serve_vs_host(cfg, run, mesh)
        print(f"smoke logits: prompt {cmp['prompt_len']} tokens, chip bf16 "
              f"vs host float32: max |diff| {cmp['max_abs']:.5f}, RMS diff "
              f"{cmp['rel_rms']:.5f} of the RMS")
    else:
        cmp = sharded_vs_one_chip(cfg, run, mesh)
        print(f"smoke logits: {args.chips} chips vs 1, all prompts: max "
              f"|diff| {cmp['max_abs']:.5f}, RMS diff {cmp['rel_rms']:.5f} "
              f"of the RMS")
    del run               # free the bf16 weights and cache for the next run
    run32 = float32_phase(cfg, mesh)
    s32 = run32.summary
    print(f"smoke float32: {int(s32['completed'])}/{len(run32.requests)} "
          f"completed, {int(s32['failures'])} failures, "
          f"{int(s32['restores'])} snapshot restores, tokens equal "
          f"greedy_reference for every request; wall {run32.wall_s:.3f} s")
    del run32
    if args.chips == 1:
        sch = scheduler_phase()
        print(f"smoke scheduler: montage {sch['tasks']} tasks x "
              f"{sch['features']} PCA features, pallas kernel compiled="
              f"{sch['compiled_kernel']}, max |diff| vs jnp "
              f"{sch['max_abs']:.3g}, replication counts identical "
              f"{sch['rep_histogram']}")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"smoke device: backend compile {compile_s[0]:.1f} s, "
          f"peak_bytes_in_use on device 0 {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
