"""Shared set-up of the benchmark's tests: ``bench/`` on the import path
and a small bench directory (tiny configurations, a short mix) built in a
temporary directory from the real reference files and metric readers."""
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

# every configuration of the benchmark, found by its file, and the model
# fields it changes to run small on the CPU (its ``tiny``)
CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
TINY = {c: json.loads((BENCH / "configs" / f"{c}.json").read_text())["tiny"]
        for c in CONFIGS}
MIX = {"interarrival": {"dist": "gamma", "shape": 1.0},
       "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                      "min": 8, "max": 64},
       "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 4, "max": 16},
       "schedule_seed": 0, "env": "normal", "policy": "crch"}


def limit_of(config: str) -> float:
    """The widest logit gap the cells of ``config`` allow (any one: the
    cells of one configuration share it)."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = next(w["name"] for w in bench["workloads"]
                if w["config"] == config)
    return json.loads((BENCH / "cells" / f"{cell}.json").read_text()
                      )["check"]["max_logit_gap"]


def make_bench(root: Path, config: str, *, rate_rps=20.0, limit=0.25,
               min_tokens=32) -> dict:
    """A bench dir under ``root`` with one cell ``<config>.tiny`` and the
    parsed BENCHMARK.json to run it with."""
    for sub in ("configs", "traffic", "cells"):
        (root / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", root / "metrics", dirs_exist_ok=True)
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cfg["model"].update(TINY[config])
    cfg["deployment"].update(workers=2, slots_per_worker=2, cache_len=80)
    (root / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    shutil.copy(BENCH / "configs" / f"{config}.py", root / "configs")
    (root / "traffic" / "tiny.json").write_text(json.dumps(MIX))
    (root / "cells" / f"{config}.tiny.json").write_text(json.dumps(
        {"rate_rps": rate_rps,
         "check": {"max_logit_gap": limit, "min_tokens": min_tokens,
                   "sample_tokens": 4 * min_tokens, "max_requests": 6}}))
    (root / "peaks.json").write_text(json.dumps(
        {"source": "test", "devices": {"cpu": {
            "bf16_flops": 1e12, "hbm_bytes_s": 1e11}}}))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": f"{config}.tiny", "config": config,
                           "traffic": "tiny", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    return bench


@pytest.fixture
def tiny_bench(tmp_path):
    return lambda config, **kw: (make_bench(tmp_path, config, **kw), tmp_path)
