"""Readings that set a cell's correctness limit: the program's widest
logit gap on many seeds, and the lower-precision control's on the same
requests.

For each seed, in one process: serve the cell's traffic for ``--seconds``
at the cell's rate, exactly as a run does, free the program, and run the
run's own comparison (``cellrun.check_outputs``) over the same seeded
sample of finished requests.  The program's
reading is the widest gap of a served token (as in a run).  The control's
is the reference computed with every matmul operand rounded to
float8_e4m3fn, one step below the configuration's bfloat16: at each
position the token it puts first, its gap read in the float32 logits.

    python3 bench/tools/readings.py --workload deepseek-coder-33b.chat \
        --seeds 1,2,3 --seconds 25 --out readings.jsonl
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import adapter, device, spec, window  # noqa: E402
from harness.cellrun import build, check_outputs  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    device.require_tpu(cell.chips)
    adapter.compile_cache()
    with open(args.out, "a") as out:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t = time.time()
            system, specs, m = build(cell, seed=seed, seconds=args.seconds)
            res = window.run(system, specs, seconds=args.seconds)
            outputs = system.outputs()
            system.free()
            prog, ctrl = check_outputs(cell, m, seed, outputs, res, specs,
                                       control=bool(args.control))
            row = {"workload": args.workload, "seed": seed,
                   "seconds_window": args.seconds, "finished": len(outputs),
                   "program_max_gap": prog["max_logit_gap"]["value"],
                   "tokens": prog["tokens_compared"]["value"],
                   "control_max_gap": (ctrl["max_logit_gap"]["value"]
                                       if ctrl else None),
                   "seconds": time.time() - t}
            print(json.dumps(row), file=out, flush=True)
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
