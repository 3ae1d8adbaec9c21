"""Production serving launcher: fault-tolerant continuous batching.

Requests are admitted through ``repro.serve``: freed decode slots prefill
new requests while live requests keep decoding; replication follows the
selected policy (``none`` / ``all-k`` / ``crch``) and failed workers resume
requests from their last decode snapshot.  Every model family — dense, MoE,
RWKV, RG-LRU hybrid, encoder-decoder, multimodal — runs through the
continuous engine; ``--static`` explicitly selects the legacy one-shot
static batch (a baseline, not a fallback), and ``--verify-static`` checks
the engine's tokens token-for-token against the batch=1 static reference.

``--chips N`` runs on a ``(1, N)`` data x model mesh over the host's first
N devices: weights take the TP layout of ``repro.distributed.params`` and
the KV cache is sharded along ``kv_seq``.  On CPU, ``--tiny`` validates the
same code end-to-end.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --tiny \
        --requests 8 --prompt-len 32 --new-tokens 16 --policy crch \
        --env normal
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.chaos import SERVE_KINDS, ChaosEngine, FaultTrace, sample_trace
from repro.configs import get_config
from repro.distributed import params as pshard
from repro.distributed.sharding import use_rules
from repro.distributed.steps import make_prefill_step, make_serve_step
from repro.launch.mesh import enable_compile_cache, make_mesh
from repro.launch.shapes import make_batch
from repro.models import lm
from repro.serve import (EngineConfig, Request, ServeEngine, ServeMetrics,
                         WorkerPool, crch_policy, engine_supported,
                         greedy_reference, prompt_bucket, uniform_policy)


def make_chaos(args, *, kinds, n_targets: int, horizon: int, tracer=None):
    """Build a ChaosEngine from the --chaos* flags (None when disabled).

    ``--chaos-trace`` replays a recorded trace verbatim (bit-identical run);
    otherwise ``--chaos PROFILE`` samples a fresh trace from the profile's
    Section 4.1 distributions, optionally recorded with ``--chaos-record``.
    An obs tracer annotates every applied fault (``fault.<kind>``) and arms
    the flight recorder's dump-on-fault trigger.
    """
    if args.chaos_trace:
        trace = FaultTrace.load(args.chaos_trace)
    elif args.chaos != "none":
        trace = sample_trace(args.chaos, horizon=horizon,
                             n_targets=n_targets, seed=args.chaos_seed,
                             kinds=kinds)
    else:
        return None
    if args.chaos_record:
        trace.save(args.chaos_record)
    print(f"chaos: {len(trace)} events over {sorted(trace.kinds())} "
          f"(meta={trace.meta})")
    return ChaosEngine(trace, tracer=tracer)


def add_trace_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--trace-dir", default="",
                    help="enable the repro.obs flight recorder; JSONL + "
                         "Chrome trace dumps and metrics land here")
    ap.add_argument("--trace-dump-on-fault", action="store_true",
                    help="dump the recorder window on every fault injected "
                         "and every recovery path taken")
    ap.add_argument("--trace-capacity", type=int, default=8192,
                    help="flight-recorder ring capacity (events)")
    ap.add_argument("--trace-window-s", type=float, default=0.0,
                    help="dump only the last N seconds of the ring "
                         "(0 = the whole ring)")


def make_obs(args) -> obs.ObsContext:
    """Build the run's ObsContext from the --trace* flags.  Without
    ``--trace-dir`` this is the NULL tracer + a detached registry."""
    return obs.setup(args.trace_dir or None,
                     dump_on_fault=args.trace_dump_on_fault,
                     capacity=args.trace_capacity,
                     window_s=args.trace_window_s or None)


def add_chaos_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--chaos", choices=("none", "stable", "normal",
                                        "unstable"), default="none",
                    help="sample a multi-fault chaos trace from this profile")
    ap.add_argument("--chaos-trace", default="",
                    help="replay a recorded fault trace (JSON) verbatim")
    ap.add_argument("--chaos-record", default="",
                    help="record the active fault trace to this path")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--chaos-horizon", type=int, default=0,
                    help="trace horizon in steps (0 = derive from the run)")
    ap.add_argument("--chaos-assert", action="store_true",
                    help="CI smoke: require survival — completions with "
                         "nonzero restores/resubmissions and zero "
                         "past-first-token drops")


def _sharded_params(cfg, mesh, seed: int):
    """Initialise the weights directly into their mesh layout (no full
    unsharded copy on the first device)."""
    def init():
        return lm.init_params(jax.random.key(seed), cfg)

    psh = pshard.param_shardings(jax.eval_shape(init), mesh, zero1=True)
    return jax.jit(init, out_shardings=psh)()


def _make_requests(cfg, n: int, prompt_len: int, new_tokens: int,
                   seed: int, min_prompt_len: int = 0) -> list[Request]:
    rng = np.random.default_rng(seed)
    lo = max(min_prompt_len or prompt_len // 2, 4)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(lo, prompt_len + 1))
        newt = new_tokens if i % 3 else new_tokens * 2
        frames = (rng.normal(size=(cfg.n_frames, cfg.d_model))
                  .astype(np.float32) if cfg.is_encdec else None)
        embeds = (rng.normal(size=(cfg.n_image_tokens, cfg.d_model))
                  .astype(np.float32) if cfg.n_image_tokens else None)
        reqs.append(Request(
            rid=i, prompt=rng.integers(1, cfg.vocab_size, plen,
                                       dtype=np.int64).astype(np.int32),
            max_new_tokens=newt, arrival=0,
            deadline=16 * (plen + newt),
            frames=frames, image_embeds=embeds))
    return reqs


@dataclasses.dataclass
class ServeRun:
    """What one continuous-engine run leaves behind for its caller."""
    engine: ServeEngine
    requests: list[Request]
    params: dict              # the float32 weights the engine was built from
    cache_len: int
    wall_s: float
    summary: dict
    reference: dict | None    # rid -> greedy_reference tokens (--verify-static)


def continuous_main(cfg, mesh, args, *, record_logits: bool = False
                    ) -> ServeRun:
    """Serve ``args.requests`` seeded requests through :class:`ServeEngine`
    on ``mesh``, print the run's numbers and check what the flags ask for
    (``--chaos-assert``, ``--verify-static``).  ``record_logits`` keeps the
    engine's logits (``EngineConfig.record_logits``)."""
    reqs = _make_requests(cfg, args.requests, args.prompt_len,
                          args.new_tokens, args.seed, args.min_prompt_len)
    offset = cfg.n_image_tokens or 0
    cache_len = max(offset + prompt_bucket(r.prompt_len) + r.max_new_tokens
                    for r in reqs)
    if cfg.rglru and cfg.window:
        cache_len = max(cache_len, cfg.window)
    if args.policy == "crch":
        policy = crch_policy(reqs)
    elif args.policy == "all":
        policy = uniform_policy(args.max_rep)
    else:
        policy = uniform_policy(1)
    pool = WorkerPool(args.workers, args.slots_per_worker,
                      environment=(args.env if args.env != "none" else None),
                      seed=args.seed)
    horizon = args.chaos_horizon or min(
        args.max_steps, 8 * max(r.max_new_tokens for r in reqs))
    ctx = make_obs(args)
    chaos = make_chaos(args, kinds=SERVE_KINDS, n_targets=args.workers,
                       horizon=horizon, tracer=ctx.tracer)
    with use_rules(mesh):
        params = _sharded_params(cfg, mesh, args.seed)
        engine = ServeEngine(
            cfg, EngineConfig(cache_len=cache_len, q_chunk=64,
                              max_queue_depth=args.max_queue_depth or None,
                              record_logits=record_logits),
            pool=pool, policy=policy, params=params,
            metrics=ServeMetrics(registry=ctx.registry), chaos=chaos,
            tracer=ctx.tracer)
        for r in reqs:
            engine.submit(r)
        t0 = time.time()
        metrics = engine.run(max_steps=args.max_steps)
        wall = time.time() - t0
        if any(leaf.is_deleted() for leaf in jax.tree.leaves(params)):
            # the engine took the weights and released those it cast:
            # the float32 ones the reference reads are made again
            params = _sharded_params(cfg, mesh, args.seed)
    s = metrics.summary(engine.step_no)
    tok_s = metrics.decode_tokens / max(wall, 1e-9)
    print(f"arch={cfg.name} ({cfg.param_count() / 1e6:.0f}M params) "
          f"requests={args.requests} slots={pool.n_slots} "
          f"policy={policy.name} env={args.env} chips={args.chips}")
    print(f"{engine.step_no} engine steps in {wall:.2f}s "
          f"({tok_s:.1f} tok/s aggregate) | completed "
          f"{int(s['completed'])}/{args.requests} "
          f"(in-deadline {int(s['in_deadline'])}) | "
          f"p50/p99 latency {s['p50_latency']:.0f}/{s['p99_latency']:.0f} "
          f"steps")
    print(f"usage {s['usage_tokens']:.0f} tok | wasted "
          f"{s['wasted_tokens']:.0f} tok ({100 * s['wastage_frac']:.1f}%) | "
          f"failures {int(s['failures'])} resubmissions "
          f"{int(s['resubmissions'])} snapshot-restores "
          f"{int(s['restores'])} rejected-on-arrival "
          f"{int(s['rejected_on_arrival'])}")
    if chaos is not None:
        print(f"chaos applied: {dict(chaos.applied_by_kind)} | shed "
              f"{int(s['shed'])} hedge-drops {int(s['hedge_drops'])} "
              f"snapshot-verify-fails {int(s['snapshot_restore_failures'])} "
              f"past-first-token drops {int(s['past_first_drops'])}")
    done = sorted(engine.completed)
    assert done, "no requests completed"
    print("sample:", engine.completed[done[0]][:12])
    if ctx.finish() is not None:
        rec = ctx.recorder
        print(f"trace: {len(rec.dumps)} dump(s) + metrics under "
              f"{args.trace_dir} (faults seen "
              f"{dict(rec.faults_seen)}, recoveries "
              f"{dict(rec.recoveries_seen)})")
    if args.chaos_assert:
        assert chaos is not None, "--chaos-assert needs an active chaos run"
        assert chaos.applied, "chaos trace fired no events"
        assert s["completed"] > 0, "no requests survived the chaos run"
        recoveries = int(s["restores"]) + int(s["resubmissions"])
        assert recoveries > 0, (
            "chaos run exercised no recovery path "
            f"(restores+resubmissions == 0, applied "
            f"{dict(chaos.applied_by_kind)})")
        assert s["past_first_drops"] == 0, (
            f"{int(s['past_first_drops'])} request(s) dropped past their "
            f"first token — degraded mode must never shed live work")
        print(f"chaos-assert OK: {int(s['completed'])} completed, "
              f"{recoveries} recoveries, 0 past-first-token drops")
    ref = None
    if args.verify_static:
        with use_rules(mesh):
            ref = greedy_reference(params, cfg, reqs, cache_len, q_chunk=64)
        mismatched = [r.rid for r in reqs
                      if engine.output(r.rid) != ref[r.rid]]
        print(f"parity vs static reference: "
              f"{len(reqs) - len(mismatched)}/{len(reqs)} token-exact"
              + (f" (MISMATCH rids {mismatched})" if mismatched else ""))
        assert not mismatched, f"token parity failed for rids {mismatched}"
    return ServeRun(engine=engine, requests=reqs, params=params,
                    cache_len=cache_len, wall_s=wall, summary=s, reference=ref)


def static_main(cfg, mesh, args) -> None:
    """Legacy one-shot static batch (non-KV-cache-friendly families)."""
    cache_len = args.prompt_len + args.new_tokens + (cfg.n_image_tokens or 0)
    with use_rules(mesh):
        params = _sharded_params(cfg, mesh, args.seed)
        prefill = jax.jit(make_prefill_step(
            cfg, cache_len, q_chunk=min(1024, args.prompt_len)))
        serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,))

        batch = make_batch(cfg, batch=args.requests, seq=args.prompt_len,
                           seed=args.seed)
        prompts = {k: v for k, v in batch.items()
                   if k in ("tokens", "frames", "image_embeds")}
        t0 = time.time()
        logits, cache = prefill(params, prompts)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        jax.block_until_ready(tok)
        t_prefill = time.time() - t0

        pos0 = args.prompt_len + (cfg.n_image_tokens or 0)
        out = [np.asarray(tok)]
        t0 = time.time()
        for i in range(args.new_tokens - 1):
            tok, logits, cache = serve(params, cache, tok,
                                       jnp.int32(pos0 + i))
            out.append(np.asarray(tok))
        jax.block_until_ready(tok)
        t_decode = time.time() - t0

    gen = np.concatenate(out, axis=1)
    tok_s = args.requests * (args.new_tokens - 1) / max(t_decode, 1e-9)
    print(f"arch={cfg.name} ({cfg.param_count() / 1e6:.0f}M params) "
          f"batch={args.requests} prompt={args.prompt_len} "
          f"new={args.new_tokens} chips={args.chips} [static]")
    print(f"prefill {t_prefill * 1e3:.0f} ms | decode "
          f"{t_decode * 1e3 / max(args.new_tokens - 1, 1):.1f} ms/token "
          f"({tok_s:.1f} tok/s aggregate)")
    assert np.isfinite(np.asarray(logits)).all()
    print("sample:", gen[0][:12].tolist())


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--requests", "--batch", type=int, default=4,
                    dest="requests")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--min-prompt-len", type=int, default=0,
                    help="shortest random prompt (0 = half of --prompt-len)")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--slots-per-worker", type=int, default=2)
    ap.add_argument("--policy", choices=("none", "all", "crch"),
                    default="crch")
    ap.add_argument("--max-rep", type=int, default=3)
    ap.add_argument("--max-queue-depth", type=int, default=0,
                    help="queue-length-priced admission: reject fresh "
                         "arrivals with a retry_after hint once the queue "
                         "holds this many work items (0 = unbounded)")
    ap.add_argument("--env", choices=("none", "stable", "normal", "unstable"),
                    default="none")
    ap.add_argument("--max-steps", type=int, default=20_000)
    ap.add_argument("--static", action="store_true",
                    help="run the legacy one-shot static batch baseline")
    ap.add_argument("--verify-static", action="store_true",
                    help="check engine tokens against the batch=1 static "
                         "reference, token-for-token")
    ap.add_argument("--chips", type=int, default=1,
                    help="serve on a (1, N) data x model mesh over the "
                         "first N devices")
    ap.add_argument("--seed", type=int, default=0)
    add_chaos_args(ap)
    add_trace_args(ap)
    return ap


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    if args.static and (args.chaos != "none" or args.chaos_trace):
        raise SystemExit("--static has no fault tolerance to chaos-test; "
                         "use the continuous engine")

    cfg = get_config(args.arch, tiny=args.tiny)
    mesh = make_mesh(args.chips)
    enable_compile_cache()
    supported, why = engine_supported(cfg)
    if not supported:
        raise SystemExit(f"{args.arch}: {why}")
    if args.static:
        static_main(cfg, mesh, args)
    else:
        continuous_main(cfg, mesh, args)


if __name__ == "__main__":
    main()
