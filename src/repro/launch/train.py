"""Production training launcher.

``--chips N`` trains on a ``(1, N)`` data x model mesh over the host's first
N devices; on CPU it runs the same code end-to-end with ``--tiny`` configs
for validation.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --tiny \
        --steps 50 --global-batch 8 --seq-len 128 --ckpt-dir /tmp/ckpt

``--pods N`` (N > 1) switches to the multi-pod cluster mode: N replicated
data-parallel pods training through the partition-tolerant compressed
exchange (``repro.ft.crosspod``), with ``net_partition`` / ``disk_full``
chaos targeting the pod set.  Under ``--chaos-assert`` the run must finish
with zero split-brain fingerprint divergences, a clean committed-index
audit, and final params bit-identical to a fault-free reference cluster:

    PYTHONPATH=src python -m repro.launch.train --tiny --pods 3 \
        --steps 12 --global-batch 2 --seq-len 32 --chaos unstable \
        --chaos-seed 29 --chaos-assert
"""
from __future__ import annotations

import argparse
import tempfile
import time

import jax
import numpy as np

from repro.chaos import DISK_FULL, NET_PARTITION, TRAIN_KINDS
from repro.configs import get_config
from repro.data import DataConfig, SyntheticTokenPipeline
from repro.distributed import params as pshard
from repro.distributed.sharding import use_rules
from repro.distributed.steps import make_train_step
from repro.ft import (CheckpointStore, DynamicInterval, FaultInjector,
                      PodTrainingCluster, TrainingCoordinator, tree_digest)
from repro.launch.mesh import enable_compile_cache, make_mesh
from repro.launch.serve import (add_chaos_args, add_trace_args, make_chaos,
                                make_obs)
from repro.models import lm
from repro.obs import profile_jit, save_profiles
from repro.optim import AdamWConfig, adamw_init


def cluster_main(cfg, mesh, args) -> None:
    """Multi-pod mode: quorum trains through partitions, minority pods park
    and catch up from the quorum checkpoint at heal."""
    # --chaos-assert needs the exact per-step split-brain check; otherwise
    # fingerprints are sampled (tree_digest syncs every leaf to host)
    fingerprint_every = 1 if args.chaos_assert else args.fingerprint_every

    def build(chaos_engine, ckpt_dir, ctx=None):
        params = lm.init_params(jax.random.key(args.seed), cfg)
        pipeline = SyntheticTokenPipeline(
            DataConfig(args.global_batch, args.seq_len, seed=args.seed), cfg)
        tracer = ctx.tracer if ctx is not None else None
        return PodTrainingCluster(
            cfg=cfg, params=params, pipeline=pipeline,
            store=CheckpointStore(ckpt_dir, tracer=tracer),
            n_pods=args.pods, opt_cfg=AdamWConfig(lr=args.lr),
            q_chunk=min(1024, args.seq_len), xent_chunk=512,
            chaos=chaos_engine, fingerprint_every=fingerprint_every,
            tracer=tracer,
            registry=ctx.registry if ctx is not None else None)

    ctx = make_obs(args)
    chaos = make_chaos(args, kinds=(NET_PARTITION, DISK_FULL),
                       n_targets=args.pods,
                       horizon=args.chaos_horizon or args.steps,
                       tracer=ctx.tracer)
    with use_rules(mesh):
        cluster = build(chaos, args.ckpt_dir, ctx)
        t0 = time.time()
        report = cluster.run(args.steps)
        dt = time.time() - t0
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"pods={args.pods} steps={report.steps_completed} "
          f"rounds={report.rounds} ckpts={report.checkpoints} "
          f"compression={cluster.exchange.compression_ratio:.1f}x")
    print(f"partitions {report.partitions} parked-pod-rounds "
          f"{report.parked_pod_rounds} heals {report.heals} catchups "
          f"{report.catchups} disk-full {report.disk_full_events} "
          f"enospc-retries {report.enospc_retries} | split-brain "
          f"{report.split_brain_divergences} index-violations "
          f"{report.index_violations} | fingerprints "
          f"{report.fingerprints_taken} taken / "
          f"{report.fingerprints_skipped} skipped (every "
          f"{fingerprint_every})")
    if chaos is not None:
        print(f"chaos applied: {dict(chaos.applied_by_kind)}")
    if ctx.finish() is not None:
        print(f"trace: {len(ctx.recorder.dumps)} dump(s) + metrics under "
              f"{args.trace_dir}")
    print(f"final loss {report.final_loss:.4f} wall={dt:.1f}s "
          f"({dt / max(report.steps_completed, 1):.2f}s/step)")
    if args.chaos_assert:
        assert chaos is not None, "--chaos-assert needs an active chaos run"
        assert chaos.applied, "chaos trace fired no events"
        assert report.steps_completed == args.steps, (
            f"cluster did not survive: {report.steps_completed}/"
            f"{args.steps} steps")
        assert report.split_brain_divergences == 0, (
            f"{report.split_brain_divergences} split-brain fingerprint "
            "divergence(s): two components advanced independently")
        assert report.index_violations == 0, (
            "committed checkpoint index failed its audit after chaos")
        assert all(np.isfinite(report.losses)), "non-finite loss in cluster"
        with tempfile.TemporaryDirectory() as ref_dir, use_rules(mesh):
            reference = build(None, ref_dir)
            ref = reference.run(args.steps)
        ref_digest = tree_digest(reference.params[0])
        mismatched = [p for p in range(args.pods)
                      if tree_digest(cluster.params[p]) != ref_digest]
        assert ref.steps_completed == args.steps
        assert not mismatched, (
            f"pods {mismatched} are not bit-identical to the fault-free "
            f"reference after heal (digest {ref_digest[:12]})")
        print(f"chaos-assert OK: {report.steps_completed} steps, "
              f"{report.heals} heals, all {args.pods} pods bit-identical "
              "to the fault-free reference, 0 split-brain divergences")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-gamma-s", type=float, default=5.0)
    ap.add_argument("--chips", type=int, default=1,
                    help="train on a (1, N) data x model mesh over the "
                         "first N devices")
    ap.add_argument("--inject-mtbf-steps", type=float, default=0.0,
                    help="simulate failures every ~N steps (0 = off)")
    ap.add_argument("--pods", type=int, default=1,
                    help="N > 1: multi-pod cluster mode through the "
                         "partition-tolerant exchange")
    ap.add_argument("--fingerprint-every", type=int, default=8,
                    help="cluster mode: take the split-brain sha1 "
                         "fingerprint every N applied steps (forced to 1 "
                         "under --chaos-assert)")
    ap.add_argument("--seed", type=int, default=0)
    add_chaos_args(ap)
    add_trace_args(ap)
    return ap


def train_main(cfg, mesh, args):
    """Single-pod training under the coordinator; returns its report."""
    ctx = make_obs(args)
    with use_rules(mesh):
        params = lm.init_params(jax.random.key(args.seed), cfg)
        opt_state = adamw_init(params)
        abstract = jax.eval_shape(lambda: params)
        psh = pshard.param_shardings(abstract, mesh)
        params = jax.device_put(params, psh)
        step_fn = jax.jit(make_train_step(
            cfg, AdamWConfig(lr=args.lr), accum_steps=args.accum,
            q_chunk=min(1024, args.seq_len), xent_chunk=512,
            total_steps=args.steps))
        profiled = None
        if ctx.enabled:
            # the wrapper blocks on outputs each call (exact wall times at
            # the cost of dispatch overlap) — opt-in with --trace-dir
            profiled = profile_jit(step_fn, name="train_step",
                                   registry=ctx.registry, tracer=ctx.tracer)
            step_fn = profiled

        pipeline = SyntheticTokenPipeline(
            DataConfig(args.global_batch, args.seq_len, seed=args.seed), cfg)
        injector = (FaultInjector(mtbf_steps=args.inject_mtbf_steps,
                                  seed=args.seed,
                                  horizon_steps=args.steps)
                    if args.inject_mtbf_steps else None)
        chaos = make_chaos(args, kinds=TRAIN_KINDS, n_targets=1,
                           horizon=args.chaos_horizon or args.steps,
                           tracer=ctx.tracer)
        coord = TrainingCoordinator(
            train_step=step_fn, params=params, opt_state=opt_state,
            pipeline=pipeline,
            store=CheckpointStore(args.ckpt_dir, tracer=ctx.tracer),
            interval=DynamicInterval(gamma_s=args.ckpt_gamma_s),
            injector=injector, chaos=chaos, tracer=ctx.tracer,
            registry=ctx.registry)

        t0 = time.time()
        report = coord.run(args.steps)
        dt = time.time() - t0

    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"steps={report.steps_completed} failures={report.failures} "
          f"restores={report.restores} ckpts={report.checkpoints}")
    if chaos is not None:
        print(f"chaos applied: {dict(chaos.applied_by_kind)} | "
              f"nan-rollbacks {report.nan_rollbacks} skipped-batches "
              f"{report.skipped_batches} ckpt-fallbacks "
              f"{report.ckpt_fallbacks} ckpt-corruptions "
              f"{report.ckpt_corruptions} slowdowns {report.slowdowns} "
              f"backoff {report.backoff_steps:.0f} steps | partitions "
              f"{report.partitions} parked {report.parked_steps:.0f} "
              f"disk-full {report.disk_full_events} enospc-retries "
              f"{report.enospc_retries} index-violations "
              f"{report.index_violations}")
    n = max(1, len(report.losses) // 10)
    first = float(np.mean(report.losses[:n]))
    last = float(np.mean(report.losses[-n:]))
    print(f"loss: first10%={first:.4f} last10%={last:.4f} "
          f"({'improved' if last < first else 'NOT improved'}) "
          f"wall={dt:.1f}s ({dt / max(report.steps_completed, 1):.2f}s/step)")
    if profiled is not None:
        profiled.capture_cost(coord.params, coord.opt_state,
                              coord.pipeline.batch_at(0))
        prof = profiled.report()
        mean_ms = (prof["mean_s"] or 0.0) * 1e3
        print(f"profile: compile {prof['compile_s'] or 0.0:.2f}s, "
              f"{prof['calls']} steps mean {mean_ms:.1f} ms"
              + (f", {prof['flops']:.3g} FLOP/step"
                 if prof["flops"] else ""))
        save_profiles(f"{args.trace_dir}/profile.json", [profiled])
    if ctx.finish() is not None:
        rec = ctx.recorder
        print(f"trace: {len(rec.dumps)} dump(s) + metrics under "
              f"{args.trace_dir} (faults seen {dict(rec.faults_seen)}, "
              f"recoveries {dict(rec.recoveries_seen)})")
    if args.chaos_assert:
        assert chaos is not None, "--chaos-assert needs an active chaos run"
        assert chaos.applied, "chaos trace fired no events"
        assert report.steps_completed == args.steps, (
            f"training did not survive: {report.steps_completed}/"
            f"{args.steps} steps")
        assert report.restores > 0, "chaos run exercised no restore path"
        assert report.index_violations == 0, (
            "committed checkpoint index failed its audit after chaos")
        assert all(np.isfinite(report.losses)), "non-finite loss escaped the "\
            "NaN guard"
        print(f"chaos-assert OK: {report.steps_completed} steps, "
              f"{report.restores} restores, all losses finite, "
              "committed index clean")
    return report


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    cfg = get_config(args.arch, tiny=args.tiny)
    mesh = make_mesh(args.chips)
    enable_compile_cache()
    if args.pods > 1:
        cluster_main(cfg, mesh, args)
    else:
        train_main(cfg, mesh, args)


if __name__ == "__main__":
    main()
