"""Fault-tolerant continuous-batching demo (repro.serve).

Submits a handful of requests with mixed prompt/decode lengths to the
slot-based engine, kills a worker mid-decode, and shows the affected
requests resuming from their latest decode snapshot with byte-identical
output (greedy decoding is deterministic).

    PYTHONPATH=src python examples/serve_continuous.py [--arch olmo-1b]
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve import (EngineConfig, Request, ServeEngine,  # noqa: E402
                         WorkerPool, crch_policy, prompt_bucket)


def make_requests(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(8, 24))
        newt = 8 if i % 3 else 24
        reqs.append(Request(
            rid=i,
            prompt=rng.integers(1, cfg.vocab_size, plen,
                                dtype=np.int64).astype(np.int32),
            max_new_tokens=newt, arrival=0, deadline=10_000))
    return reqs


def run(cfg, params, reqs, *, fail_at=None):
    cache_len = max(prompt_bucket(r.prompt_len) + r.max_new_tokens
                    for r in reqs)
    pool = WorkerPool(2, 2, mtbf_steps=None, mttr_steps=6, seed=0)
    engine = ServeEngine(cfg, EngineConfig(cache_len=cache_len, q_chunk=32,
                                           snapshot_lambda=4),
                         pool=pool, policy=crch_policy(reqs), params=params)
    for r in reqs:
        engine.submit(r)
    while engine.pending() and engine.step_no < 5_000:
        if fail_at is not None and engine.step_no == fail_at:
            pool.force_failure(engine.step_no, wid=0)
            print(f"  [step {engine.step_no}] worker 0 killed "
                  f"(back after {pool.mttr_steps} steps)")
        engine.step()
    return engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--requests", type=int, default=6)
    args = ap.parse_args()

    cfg = get_config(args.arch, tiny=True)
    # the serving weights once, for both runs' engines
    params = lm.serving_params(lm.init_params(jax.random.key(0), cfg), cfg)
    reqs = make_requests(cfg, args.requests)

    print("clean run (no failures):")
    clean = run(cfg, params, reqs)
    print(f"  {len(clean.completed)}/{len(reqs)} completed in "
          f"{clean.step_no} steps")

    print("faulty run (worker 0 dies mid-decode):")
    faulty = run(cfg, params, reqs, fail_at=12)
    s = faulty.metrics.summary(faulty.step_no)
    print(f"  {len(faulty.completed)}/{len(reqs)} completed in "
          f"{faulty.step_no} steps | resubmissions "
          f"{int(s['resubmissions'])}, snapshot restores "
          f"{int(s['restores'])}")

    for rid in sorted(clean.completed):
        assert clean.completed[rid] == faulty.completed[rid], rid
    print("tokens after failure + snapshot resume are byte-identical "
          "to the failure-free run")
    print("sample:", clean.completed[0][:10])


if __name__ == "__main__":
    main()
