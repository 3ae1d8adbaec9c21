"""Chaos matrix: fault-class x recovery-path survival/goodput grid.

One row per (layer, fault class) cell of the ``repro.chaos`` taxonomy: each
cell replays a single-kind fault trace against the layer that owns the
recovery path —

* **serve** rows drive the continuous-batching engine (tiny config) and
  report completions, goodput, and the degraded-mode counters (shed /
  hedge-drops / snapshot-verify failures / past-first-token drops);
* **train** rows drive the fault-tolerant training coordinator and report
  steps survived, restores, checkpoint fallbacks, and NaN rollbacks.

A cell *survives* when every request is accounted for (completed or
deliberately shed, never dropped past its first token) respectively when
training reaches the target step with only finite losses.  Cells whose
sampled trace would be empty get one forced event so every recovery path is
exercised; ``ckpt_corrupt`` / ``snapshot_corrupt`` / ``disk_full`` events
are paired with a follow-up ``host_crash`` so the corrupted (resp. pruned)
state is actually *read* (the fallback is the interesting part, not the
flip).  The ``train/net_partition`` cell runs a 3-pod
``repro.ft.PodTrainingCluster`` against a fault-free reference and demands
the healed pods land bit-identical to it at equal step count with zero
split-brain fingerprint divergences.

Record/replay: ``--record DIR`` writes each cell's trace as JSON;
``--replay DIR`` re-runs from those files with **no RNG at all** — two
replays of the same directory produce byte-identical ``--out`` grids.
``--trace-dir DIR`` additionally runs every cell under the ``repro.obs``
flight recorder (dump-on-fault); tracing consumes no RNG and touches no
counters, so a traced replay's grid stays byte-identical to an untraced
one.  ``--only layer/kind[,layer/kind...]`` restricts the grid to the
named cells.

    PYTHONPATH=src python benchmarks/chaos_matrix.py --record /tmp/tr \
        --out /tmp/grid_a.json
    PYTHONPATH=src python benchmarks/chaos_matrix.py --replay /tmp/tr \
        --out /tmp/grid_b.json   # byte-identical to a third replay run
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, "src")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.chaos import (CHAOS_PROFILES, CKPT_CORRUPT,  # noqa: E402
                         DISK_FULL, HOST_CRASH, NET_PARTITION, SERVE_KINDS,
                         SNAPSHOT_CORRUPT, TRAIN_KINDS, ChaosEngine,
                         FaultEvent, FaultTrace, sample_trace)
from repro.configs import get_config  # noqa: E402
from repro.data import DataConfig, SyntheticTokenPipeline  # noqa: E402
from repro.distributed.steps import make_train_step  # noqa: E402
from repro.ft import (CheckpointStore, DynamicInterval,  # noqa: E402
                      PodTrainingCluster, TrainingCoordinator, tree_digest)
from repro.models import lm  # noqa: E402
from repro.optim import AdamWConfig, adamw_init  # noqa: E402
from repro.serve import (EngineConfig, Request, ServeEngine,  # noqa: E402
                         WorkerPool, format_table, prompt_bucket,
                         uniform_policy)

# corruption cells pair each flip with a same-step host_crash so the
# corrupted state is read immediately (fault application always precedes
# failure processing within a step): the restore MUST take the fallback /
# re-prefill path before a fresh checkpoint or snapshot can paper over it
CRASH_LAG = 0


def cell_trace(profile: str, layer: str, kind: str, *, horizon: int,
               n_targets: int, seed: int) -> FaultTrace:
    """Single-kind trace for one matrix cell, guaranteed non-empty."""
    spec = CHAOS_PROFILES[profile]
    mttr = int(spec["mttr_steps"])
    trace = sample_trace(profile, horizon=horizon, n_targets=n_targets,
                         seed=seed, kinds=(kind,))
    if not trace.events:
        trace.events.append(FaultEvent(
            step=horizon // 3, kind=kind, targets=(0,), duration=mttr,
            seed=seed * 7919 + 1))
        trace.meta["forced"] = True
    # disk_full joins the paired-crash set: the follow-up restore must read
    # the committed index *after* the prune-and-retry rewrote it
    if kind in (CKPT_CORRUPT, SNAPSHOT_CORRUPT, DISK_FULL):
        crashes = [FaultEvent(step=ev.step + CRASH_LAG, kind=HOST_CRASH,
                              targets=tuple(range(n_targets)),
                              duration=mttr, seed=ev.seed + 1)
                   for ev in trace.events]
        trace.events = sorted(trace.events + crashes,
                              key=lambda e: (e.step, e.kind, e.targets))
        trace.meta["paired_crash_lag"] = CRASH_LAG
    trace.meta["layer"] = layer
    trace.meta["cell"] = kind
    return trace


def serve_workload(cfg, n: int, seed: int) -> list[Request]:
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        plen = int(rng.integers(6, 24))
        newt = 24 if rid % 4 == 0 else 8
        arrival = int(rng.integers(0, 40))
        reqs.append(Request(
            rid=rid,
            prompt=rng.integers(1, cfg.vocab_size, plen,
                                dtype=np.int64).astype(np.int32),
            max_new_tokens=newt, arrival=arrival,
            deadline=arrival + 10 * (plen + newt)))
    return reqs


def run_serve_cell(cfg, params, trace: FaultTrace, *, n_requests: int,
                   max_steps: int, seed: int, tracer=None) -> dict:
    reqs = serve_workload(cfg, n_requests, seed + 17)
    cache_len = max(prompt_bucket(r.prompt_len) + r.max_new_tokens
                    for r in reqs)
    pool = WorkerPool(4, 2, seed=seed)   # chaos supplies every fault
    engine = ServeEngine(
        cfg, EngineConfig(cache_len=cache_len, q_chunk=64,
                          snapshot_lambda=4),
        pool=pool, policy=uniform_policy(2), params=params,
        chaos=ChaosEngine(trace, tracer=tracer), tracer=tracer)
    for r in reqs:
        engine.submit(r)
    m = engine.run(max_steps=max_steps)
    s = m.summary(engine.step_no)
    accounted = int(s["completed"]) + int(s["shed"])
    survived = (accounted == n_requests and s["past_first_drops"] == 0)
    return {
        "layer": "serve", "fault": trace.meta["cell"],
        "events": float(len(trace)), "survived": float(survived),
        "completed": s["completed"], "in_deadline": s["in_deadline"],
        "goodput": s["goodput"], "restores": s["restores"],
        "resubmissions": s["resubmissions"], "shed": s["shed"],
        "hedge_drops": s["hedge_drops"],
        "snap_fail": s["snapshot_restore_failures"],
        "past_first": s["past_first_drops"], "steps": float(engine.step_no),
    }


def run_train_cell(cfg, trace: FaultTrace, *, n_steps: int,
                   seed: int, tracer=None) -> dict:
    params = lm.init_params(jax.random.key(seed), cfg)
    step_fn = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-3),
                                      q_chunk=64, xent_chunk=512,
                                      total_steps=n_steps))
    pipeline = SyntheticTokenPipeline(DataConfig(4, 64, seed=seed), cfg)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        coord = TrainingCoordinator(
            train_step=step_fn, params=params,
            opt_state=adamw_init(params), pipeline=pipeline,
            store=CheckpointStore(ckpt_dir, tracer=tracer),
            # tight cadence (~every 3 steps): the ckpt_corrupt cell needs a
            # predecessor checkpoint for the fallback restore to land on
            interval=DynamicInterval(gamma_s=0.5, lam_min=2.0,
                                     prior_mtbf_s=10.0),
            chaos=ChaosEngine(trace, tracer=tracer), tracer=tracer)
        rep = coord.run(n_steps)
    survived = (rep.steps_completed == n_steps
                and bool(np.all(np.isfinite(rep.losses)))
                and rep.index_violations == 0)
    return {
        "layer": "train", "fault": trace.meta["cell"],
        "events": float(len(trace)), "survived": float(survived),
        "steps": float(rep.steps_completed),
        "restores": float(rep.restores),
        "ckpt_fallbacks": float(rep.ckpt_fallbacks),
        "ckpt_corruptions": float(rep.ckpt_corruptions),
        "nan_rollbacks": float(rep.nan_rollbacks),
        "slowdowns": float(rep.slowdowns),
        "backoff": float(rep.backoff_steps),
        "wasted": float(rep.wasted_steps),
        "disk_full": float(rep.disk_full_events),
        "enospc_retries": float(rep.enospc_retries),
        "index_viol": float(rep.index_violations),
    }


def run_partition_cell(cfg, trace: FaultTrace, *, n_steps: int,
                       seed: int, tracer=None) -> dict:
    """net_partition cell: a 3-pod :class:`PodTrainingCluster` rides the
    trace (quorum trains, minority parks, heal catches up from the quorum
    checkpoint) next to a fault-free reference cluster.  The cell survives
    only when the healed cluster's pods all land **bit-identical** to the
    reference params at equal applied-step count, with zero split-brain
    fingerprint divergences and a clean committed-index audit."""
    def build(chaos, ckpt_dir, trc=None):
        return PodTrainingCluster(
            cfg=cfg, params=lm.init_params(jax.random.key(seed), cfg),
            pipeline=SyntheticTokenPipeline(DataConfig(2, 32, seed=seed),
                                            cfg),
            store=CheckpointStore(ckpt_dir, tracer=trc), n_pods=3,
            ckpt_every=4, chaos=chaos, tracer=trc)

    with tempfile.TemporaryDirectory() as da, \
            tempfile.TemporaryDirectory() as db:
        cluster = build(ChaosEngine(trace, tracer=tracer), da, tracer)
        rep = cluster.run(n_steps)
        reference = build(None, db)
        ref = reference.run(n_steps)
        ref_digest = tree_digest(reference.params[0])
        bit_identical = all(tree_digest(cluster.params[p]) == ref_digest
                            for p in range(cluster.n_pods))
    survived = (rep.steps_completed == n_steps
                and ref.steps_completed == n_steps
                and rep.split_brain_divergences == 0
                and bit_identical
                and bool(np.all(np.isfinite(rep.losses)))
                and rep.index_violations == 0)
    return {
        "layer": "train", "fault": trace.meta["cell"],
        "events": float(len(trace)), "survived": float(survived),
        "steps": float(rep.steps_completed),
        "rounds": float(rep.rounds),
        "partitions": float(rep.partitions),
        "parked": float(rep.parked_pod_rounds),
        "heals": float(rep.heals),
        "catchups": float(rep.catchups),
        "fp_div": float(rep.split_brain_divergences),
        "bit_identical": float(bit_identical),
        "index_viol": float(rep.index_violations),
    }


def trace_path(d: str, layer: str, kind: str) -> str:
    return os.path.join(d, f"{layer}_{kind}.json")


def run_matrix(args) -> list[dict]:
    cfg = get_config(args.arch, tiny=True)
    # the serving weights once, for every serve cell's engine
    serve_params = lm.serving_params(
        lm.init_params(jax.random.key(args.seed), cfg), cfg)
    ctx = obs.setup(getattr(args, "trace_dir", "") or None,
                    dump_on_fault=True)
    tracer = ctx.tracer if ctx.enabled else None
    rows = []
    all_cells = ([("serve", k) for k in SERVE_KINDS] +
                 [("train", k) for k in TRAIN_KINDS])
    # pair each cell with its position in the FULL grid before filtering:
    # --only must not shift the per-cell trace seeds
    cells = list(enumerate(all_cells))
    only = {c.strip() for c in getattr(args, "only", "").split(",")
            if c.strip()}
    if only:
        unknown = only - {f"{lay}/{k}" for lay, k in all_cells}
        if unknown:
            raise SystemExit(f"--only: unknown cells {sorted(unknown)}")
        cells = [(i, (lay, k)) for i, (lay, k) in cells
                 if f"{lay}/{k}" in only]
    for i, (layer, kind) in cells:
        horizon = args.serve_horizon if layer == "serve" else args.steps
        if args.replay:
            trace = FaultTrace.load(trace_path(args.replay, layer, kind))
        else:
            n_targets = (4 if layer == "serve"
                         else 3 if kind == NET_PARTITION else 1)
            trace = cell_trace(args.profile, layer, kind, horizon=horizon,
                               n_targets=n_targets,
                               seed=args.seed * 101 + i)
        if args.record:
            os.makedirs(args.record, exist_ok=True)
            trace.save(trace_path(args.record, layer, kind))
        if layer == "serve":
            rows.append(run_serve_cell(
                cfg, serve_params, trace, n_requests=args.requests,
                max_steps=args.max_steps, seed=args.seed, tracer=tracer))
        elif kind == NET_PARTITION:
            rows.append(run_partition_cell(cfg, trace, n_steps=args.steps,
                                           seed=args.seed, tracer=tracer))
        else:
            rows.append(run_train_cell(cfg, trace, n_steps=args.steps,
                                       seed=args.seed, tracer=tracer))
        print(f"[{rows[-1]['layer']}/{rows[-1]['fault']}] "
              f"survived={int(rows[-1]['survived'])} "
              f"events={int(rows[-1]['events'])}", file=sys.stderr)
    if ctx.finish() is not None:
        print(f"trace: {len(ctx.recorder.dumps)} dump(s) under "
              f"{args.trace_dir}", file=sys.stderr)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--profile", default="unstable",
                    choices=sorted(CHAOS_PROFILES))
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--steps", type=int, default=30,
                    help="training steps per train cell")
    ap.add_argument("--serve-horizon", type=int, default=200)
    ap.add_argument("--max-steps", type=int, default=2_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--record", default="",
                    help="write each cell's fault trace into this directory")
    ap.add_argument("--replay", default="",
                    help="replay traces from this directory (no RNG)")
    ap.add_argument("--out", default="",
                    help="write the grid as JSON (deterministic: replaying "
                         "the same traces reproduces it byte-identically)")
    ap.add_argument("--trace-dir", default="",
                    help="run cells under the repro.obs flight recorder "
                         "(dump-on-fault); does not perturb the grid")
    ap.add_argument("--only", default="",
                    help="comma-separated layer/kind cells to run "
                         "(e.g. serve/host_crash,train/net_partition)")
    args = ap.parse_args()

    rows = run_matrix(args)
    serve_cols = [("fault", "fault"), ("events", "events"),
                  ("survived", "ok"), ("completed", "done"),
                  ("in_deadline", "slo"), ("goodput", "goodput/1k"),
                  ("restores", "restore"), ("resubmissions", "resub"),
                  ("shed", "shed"), ("hedge_drops", "hedge-"),
                  ("snap_fail", "snapfail"), ("past_first", "pfdrop")]
    train_cols = [("fault", "fault"), ("events", "events"),
                  ("survived", "ok"), ("steps", "steps"),
                  ("restores", "restore"), ("ckpt_fallbacks", "fallback"),
                  ("ckpt_corruptions", "corrupt"),
                  ("nan_rollbacks", "nanroll"), ("slowdowns", "slow"),
                  ("backoff", "backoff"), ("wasted", "wasted"),
                  ("disk_full", "dskfull"), ("enospc_retries", "enospc"),
                  ("parked", "parked"), ("catchups", "catchup"),
                  ("fp_div", "fpdiv"), ("bit_identical", "bitid"),
                  ("index_viol", "idxviol")]
    print("== serve ==")
    print(format_table([r for r in rows if r["layer"] == "serve"],
                       serve_cols))
    print("\n== train ==")
    print(format_table([r for r in rows if r["layer"] == "train"],
                       train_cols))
    failed = [f"{r['layer']}/{r['fault']}" for r in rows
              if not r["survived"]]
    print(f"\nsurvival {len(rows) - len(failed)}/{len(rows)}"
          + (f" (FAILED: {', '.join(failed)})" if failed else ""))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
        print(f"grid -> {args.out}")
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
