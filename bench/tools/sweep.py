"""Find a cell's knee: the highest Poisson rate whose backlog does not grow
over the window.  Serves the cell's mix at each rate in turn, in one
process, and writes one JSON line per rate.

    python3 bench/tools/sweep.py --workload deepseek-coder-33b.chat \
        --rates 0.6,0.9,1.2 --seconds 40 --seed 11 --out sweep.jsonl

The backlog at time t is the requests due by t and not finished by t; it
is read at the middle and at the end of the window.  Run it once when a
cell is defined; the cell's file then holds the rate it offers.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import adapter, device, spec, window  # noqa: E402
from harness.cellrun import build  # noqa: E402
from harness.stats import percentile  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    device.require_tpu(cell.chips)
    adapter.compile_cache()
    with open(args.out, "a") as out:
        for rate in [float(r) for r in args.rates.split(",")]:
            t_set = time.time()
            system, specs, _ = build(cell, seed=args.seed,
                                     seconds=args.seconds, rate_rps=rate)
            setup = time.time() - t_set
            res = window.run(system, specs, seconds=args.seconds)
            system.free()
            ttft = [(c.first if c.first is not None else res.t1) - c.due
                    for c in res.clients.values()]
            ticks = res.ticks
            row = {
                "workload": args.workload, "rate_rps": rate,
                "setup_s": setup, "window_s": res.seconds,
                "due": len(res.clients),
                "finished": sum(c.done for c in res.clients.values()),
                "backlog_mid": res.backlog(res.t0 + res.seconds / 2),
                "backlog_end": res.backlog(res.t1),
                "delivered_tok_s": res.delivered / res.seconds,
                "ttft_p50_s": percentile(ttft, 50),
                "ttft_p90_s": percentile(ttft, 90),
                "itl_p99_s": percentile(
                    [g for c in res.clients.values() for g in c.gaps] or
                    [0.0], 99),
                "ticks": len(ticks),
                "tick_s_mean": (sum(t.t1 - t.t0 for t in ticks)
                                / max(len(ticks), 1)),
                "snapshots": sum(t.snapshots for t in ticks),
                "restores": sum(t.restores for t in ticks),
                "counters": res.counters_end,
            }
            print(json.dumps(row), file=out, flush=True)
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
