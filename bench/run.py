"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload deepseek-coder-33b.chat --seed 7 --seconds 45 \
        --trace 0

Prints progress and the numbers compared by the correctness check on
standard error, and one JSON result line as the last line of standard
output.  Exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for.  See ``harness/cellrun.py``.
"""
import time

T_PROC = time.time()

import argparse  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the float8 control's tokens in place of "
                    "the served ones; such a run must read correct false")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    from harness import device
    from harness.cellrun import run_cell
    try:
        run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), t_proc=T_PROC,
                 control=bool(args.control))
    except device.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
