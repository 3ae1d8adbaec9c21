"""Run a cell as the bounds are set: sets of runs, each run a process of
its own (``run.py``), the same seeds in each set, one after another;
then the traced runs.  Writes each run's result line with its seed, set,
exit code and wall time as one JSON line, and prints for each metric of
each set the median and the quartile spread (the distance between the
first and third quartiles of ``statistics.quantiles(values, n=4)``, as a
share of the median).

    python3 bench/tools/sets.py --workload deepseek-coder-33b.chat-overload \
        --seeds 11,12,13,14,15,16 --sets 2 --traced 21,22,23 \
        --seconds 51 --out runs.jsonl
"""
import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values) -> float | None:
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else None


def run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t = time.time()
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        line = None
    return {"seed": seed, "trace": trace, "rc": p.returncode,
            "wall_s": time.time() - t, "line": line,
            "stderr_tail": p.stderr.strip().splitlines()[-12:]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", default="")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    plan = [(k, s, 0) for k in range(args.sets) for s in seeds]
    plan += [(None, int(s), 1) for s in args.traced.split(",") if s]
    by_set: dict = {}
    with open(args.out, "a") as out:
        for k, seed, trace in plan:
            row = dict(run(args.workload, seed, args.seconds, trace),
                       workload=args.workload, set=k)
            print(json.dumps(row), file=out, flush=True)
            line = row["line"] or {}
            print(json.dumps({"set": k, "seed": seed, "rc": row["rc"],
                              "wall_s": round(row["wall_s"], 1),
                              "correct": line.get("correct"),
                              "failed": line.get("failed"),
                              "metrics": {m: v["value"] for m, v in
                                          line.get("metrics", {}).items()},
                              "checks": line.get("checks")}), flush=True)
            if row["rc"] != 0:
                print("\n".join(row["stderr_tail"]), flush=True)
            if k is not None:
                for m, v in line.get("metrics", {}).items():
                    by_set.setdefault((m, k), []).append(v["value"])
    for (m, k), values in sorted(by_set.items()):
        print(json.dumps({"metric": m, "set": k, "n": len(values),
                          "median": statistics.median(values),
                          "spread": spread(values),
                          "spread_without_first": spread(values[1:]),
                          "values": values}), flush=True)


if __name__ == "__main__":
    main()
