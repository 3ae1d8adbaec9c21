"""Run a cell once with ``--trace 1`` and keep the extracted trace events
(device modules and ops, harness host annotations) as JSON, for reading a
real trace by hand and for the reducer's recorded test trace.

    python3 bench/tools/trace_dump.py --workload deepseek-coder-33b.chat --seed 5 \
        --seconds 12 --out trace.json
"""
import time

T_PROC = time.time()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness.cellrun import run_cell  # noqa: E402

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    run_cell(a.workload, seed=a.seed, seconds=a.seconds, trace=True,
             t_proc=T_PROC, keep_trace=a.out)
