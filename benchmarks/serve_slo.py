"""Serving SLO benchmark: no-replication vs Replicate-All vs CRCH routing.

The online analogue of the paper's Figs. 8-12: a mixed request workload is
replayed through the continuous-batching engine under the stable / normal /
unstable failure environments, once per replication policy:

* ``none``   — single copy per request, restart from scratch on failure
  (the paper's plain-resubmission baseline);
* ``all-k``  — every request runs k copies (paper Replicate-All), no
  snapshots (replication is its whole fault-tolerance budget);
* ``crch``   — per-class replication learned unsupervised by the CRCH
  pipeline over request features, plus decode snapshots (the full
  CheckpointHEFT runtime of Algorithm 3).

Reports goodput (in-deadline completions), p50/p99 latency, and token
usage/wastage.  The paper's headline trade-off should reproduce online:
CRCH wastes fewer tokens than Replicate-All while completing more requests
within deadline than no-replication.

Runs standalone or as part of the ``benchmarks.run`` sweep (full mode
covers every ``_harness.ENVS`` environment; ``--quick`` is a single
normal-env olmo-1b row for smoke/overhead checks):

    PYTHONPATH=src python benchmarks/serve_slo.py --tiny
    PYTHONPATH=src python benchmarks/serve_slo.py --quick
    PYTHONPATH=src python -m benchmarks.run --only serve_slo --quick
"""
from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, "src")

try:
    from . import _harness as H
except ImportError:  # standalone: python benchmarks/serve_slo.py
    import _harness as H

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve import (EngineConfig, Request, ServeEngine,  # noqa: E402
                         WorkerPool, crch_policy, format_table,
                         prompt_bucket, uniform_policy)

POLICIES = ("none", "all", "crch")


def make_workload(cfg, *, n_short: int, n_medium: int, n_long: int,
                  arrival_spread: int, slack_factor: float,
                  seed: int) -> list[Request]:
    """Mostly-short traffic with a tail of long-decode requests — the
    failure-exposed outlier class CRCH should learn to hedge."""
    rng = np.random.default_rng(seed)
    vocab = cfg.vocab_size
    spec = ([(int(rng.integers(6, 16)), 8) for _ in range(n_short)] +
            [(int(rng.integers(16, 32)), 16) for _ in range(n_medium)] +
            [(int(rng.integers(24, 32)), 48) for _ in range(n_long)])
    rng.shuffle(spec)
    reqs = []
    for rid, (plen, newt) in enumerate(spec):
        arrival = int(rng.integers(0, arrival_spread))
        frames = (rng.normal(size=(cfg.n_frames, cfg.d_model))
                  .astype(np.float32) if cfg.is_encdec else None)
        embeds = (rng.normal(size=(cfg.n_image_tokens, cfg.d_model))
                  .astype(np.float32) if cfg.n_image_tokens else None)
        reqs.append(Request(
            rid=rid,
            prompt=rng.integers(1, vocab, plen, dtype=np.int64).astype(np.int32),
            max_new_tokens=newt, arrival=arrival,
            deadline=arrival + int(slack_factor * (plen + newt)),
            frames=frames, image_embeds=embeds))
    return reqs


def policy_for(name: str, workload: list[Request], max_rep: int):
    if name == "crch":
        return crch_policy(workload, max_rep=max_rep)
    if name == "all":
        return uniform_policy(max_rep)
    return uniform_policy(1)


def run_cell(cfg, params, workload, *, policy_name: str, env: str,
             n_workers: int, slots_per_worker: int, max_rep: int,
             max_steps: int, seed: int) -> dict:
    offset = cfg.n_image_tokens or 0
    cache_len = max(offset + prompt_bucket(r.prompt_len) + r.max_new_tokens
                    for r in workload)
    if cfg.rglru and cfg.window:
        cache_len = max(cache_len, cfg.window)
    policy = policy_for(policy_name, workload, max_rep)
    pool = WorkerPool(n_workers, slots_per_worker, environment=env,
                      seed=seed)
    # Only CRCH pairs replication with checkpointing (Algorithm 3); the
    # baselines match the paper's plain-resubmission and Replicate-All.
    ecfg = EngineConfig(cache_len=cache_len, q_chunk=64,
                        snapshots_enabled=(policy_name == "crch"))
    engine = ServeEngine(cfg, ecfg, pool=pool, policy=policy, params=params)
    for r in workload:
        engine.submit(r)
    t0 = time.perf_counter()
    metrics = engine.run(max_steps=max_steps)
    wall = time.perf_counter() - t0
    row = {"arch": cfg.name, "policy": policy.name, "env": env,
           **metrics.summary(engine.step_no)}
    row["steps"] = float(engine.step_no)
    row["wall_s"] = wall
    return row


def run(fast: bool = True, *, envs=None, seed: int = 0,
        arch: str = "olmo-1b", quick: bool = False) -> list[dict]:
    if envs is None:
        # full mode sweeps every harness environment (paper Figs. 8-12);
        # fast keeps the two that exercise failures; quick is one row-set
        envs = (("normal",) if quick
                else ("normal", "unstable") if fast else H.ENVS)
    cfg = get_config(arch, tiny=fast)
    # the serving weights once, for every cell's engine: an engine casts
    # (and releases) nothing of a tree already in the compute dtype
    params = lm.serving_params(lm.init_params(jax.random.key(seed), cfg),
                               cfg)
    if quick:
        workload_kw = dict(n_short=10, n_medium=4, n_long=2,
                           arrival_spread=60, slack_factor=4.0)
        pool_kw = dict(n_workers=3, slots_per_worker=2, max_rep=2,
                       max_steps=1_000)
    elif fast:
        workload_kw = dict(n_short=20, n_medium=8, n_long=4,
                           arrival_spread=120, slack_factor=4.0)
        pool_kw = dict(n_workers=4, slots_per_worker=2, max_rep=3,
                       max_steps=2_000)
    else:
        workload_kw = dict(n_short=120, n_medium=48, n_long=24,
                           arrival_spread=600, slack_factor=4.0)
        pool_kw = dict(n_workers=8, slots_per_worker=4, max_rep=3,
                       max_steps=10_000)
    workload = make_workload(cfg, seed=seed + 17, **workload_kw)
    rows = []
    for env in envs:
        for pol in POLICIES:
            rows.append(run_cell(cfg, params,
                                 [r for r in workload],  # fresh list
                                 policy_name=pol, env=env, seed=seed,
                                 **pool_kw))
    return H.emit("serve_slo", rows)


def check_tradeoff(rows: list[dict]) -> list[str]:
    """Paper acceptance, per (arch, env): CRCH wastes less than
    Replicate-All and completes (in deadline) at least as much as
    no-replication, strictly more in at least one environment per arch."""
    msgs = []
    by = {(r["arch"], r["env"], r["policy"]): r for r in rows}
    archs = sorted({r["arch"] for r in rows})
    envs = sorted({r["env"] for r in rows})
    for arch in archs:
        strict = False
        for env in envs:
            all_name = next(p for (a, e, p) in by
                            if a == arch and e == env and p.startswith("all"))
            none_ = by[(arch, env, "none")]
            all_ = by[(arch, env, all_name)]
            crch = by[(arch, env, "crch")]
            ok_waste = crch["wasted_tokens"] < all_["wasted_tokens"]
            ok_done = crch["in_deadline"] >= none_["in_deadline"]
            strict |= crch["in_deadline"] > none_["in_deadline"]
            msgs.append(f"[{arch}/{env}] crch wasted "
                        f"{crch['wasted_tokens']:.0f} "
                        f"< all {all_['wasted_tokens']:.0f}: "
                        f"{'OK' if ok_waste else 'FAIL'} | crch in-deadline "
                        f"{crch['in_deadline']:.0f} >= none "
                        f"{none_['in_deadline']:.0f}: "
                        f"{'OK' if ok_done else 'FAIL'}")
            if not (ok_waste and ok_done):
                msgs.append(f"[{arch}/{env}] TRADE-OFF VIOLATED")
        msgs.append(f"[{arch}] strictly more in-deadline completions than "
                    f"no-replication in >=1 env: "
                    f"{'OK' if strict else 'FAIL'}")
    return msgs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="single normal-env olmo-1b row (smoke / recorder "
                         "overhead checks)")
    ap.add_argument("--arch", nargs="+", default=["olmo-1b", "rwkv6-3b"],
                    help="architectures to sweep (one engine run per arch)")
    ap.add_argument("--envs", nargs="+",
                    default=["normal", "unstable"],
                    choices=["stable", "normal", "unstable"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    fast = not args.full
    if args.quick:
        rows = run(True, seed=args.seed, arch=args.arch[0], quick=True)
    else:
        rows = []
        for arch in args.arch:
            rows.extend(run(fast, envs=tuple(args.envs), seed=args.seed,
                            arch=arch))
    cols = [("arch", "arch"), ("env", "env"), ("policy", "policy"),
            ("n_requests", "reqs"), ("completed", "done"),
            ("in_deadline", "slo"), ("goodput", "goodput/1k"),
            ("p50_latency", "p50"), ("p99_latency", "p99"),
            ("usage_tokens", "usage"), ("wasted_tokens", "wasted"),
            ("wastage_frac", "waste%"), ("failures", "fails"),
            ("resubmissions", "resub"), ("restores", "restore"),
            ("steps", "steps"), ("wall_s", "wall_s")]
    print(format_table(rows, cols))
    if args.quick:
        return  # smoke row: too small for the paper acceptance check
    print()
    for m in check_tradeoff(rows):
        print(m)


if __name__ == "__main__":
    main()
