"""A configuration joins the benchmark by new files alone: a made-up
tiny one, with counts of its own and readers of its own (one reads a
program counter's change over the traced slice), runs through
``run_cell`` on the CPU from a bench directory that holds only new files
beside copies of the real readers."""
import io
import json
import shutil
import time

from conftest import BENCH
from harness.cellrun import run_cell

NAME = "toy-dense"

COUNTS = '''

def prefill_flops(m, prompt_len):
    return 0.0


def decode_flops(m, attended):
    return 3.0


def decode_least_bytes(m, attended, counters):
    return 1000.0 * counters["serve_tokens_total{kind=decode}"]
'''

READERS = {
    # a program counter's change between the slice's two ends
    "decode_tokens_traced": '''
KEY = "serve_tokens_total{kind=decode}"


def read(rec):
    t = rec.get("trace")
    if not t:
        return None
    return t["counters_stop"].get(KEY, 0.0) - t["counters_start"].get(KEY, 0.0)
''',
    "least_bytes_traced": '''
def read(rec):
    t = rec.get("trace")
    return t["decode_least_bytes"] if t else None
''',
    "flops_counted": '''
def read(rec):
    return rec["flops"]
''',
}


def _bench_dir(root):
    for sub in ("configs", "traffic", "cells"):
        (root / sub).mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", root / "metrics")
    for name, text in READERS.items():
        (root / "metrics" / f"{name}.py").write_text(text)
    model = {"name": NAME, "family": "dense", "n_layers": 2, "d_model": 32,
             "n_heads": 2, "n_kv_heads": 1, "d_ff": 64, "vocab_size": 128,
             "block_type": "llama", "norm_type": "rmsnorm",
             "tie_embeddings": False, "rope_theta": 10000.0,
             "param_dtype": "float32", "compute_dtype": "bfloat16"}
    (root / "configs" / f"{NAME}.json").write_text(json.dumps({
        "name": NAME, "model": model, "tiny": {},
        "reference": {"norm_eps": 1e-6, "rope_scaling": 1.0},
        "deployment": {"workers": 2, "slots_per_worker": 2,
                       "cache_len": 80}}))
    # a plain reference of the same family, and counts of its own
    ref = (BENCH / "configs" / "deepseek-coder-33b.py").read_text()
    (root / "configs" / f"{NAME}.py").write_text(ref + COUNTS)
    (root / "traffic" / "short.json").write_text(json.dumps({
        "interarrival": {"dist": "gamma", "shape": 1.0},
        "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                       "min": 8, "max": 32},
        "output_len": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                       "min": 4, "max": 24},
        "schedule_seed": 3, "env": "normal", "policy": "crch"}))
    (root / "cells" / f"{NAME}.short.json").write_text(json.dumps(
        {"rate_rps": 20.0, "check": {"max_logit_gap": 0.15,
                                     "min_tokens": 32, "sample_tokens": 128,
                                     "max_requests": 6}}))
    (root / "peaks.json").write_text(json.dumps(
        {"source": "test", "devices": {"cpu": {
            "bf16_flops": 1e12, "hbm_bytes_s": 1e11}}}))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    layer = {"unit": "1", "better": "higher", "source": "program_counter",
             "layer": "test", "moves": "delivered_tok_s"}
    bench = {"configs": [{"name": NAME}],
             "workloads": [{"name": f"{NAME}.short", "config": NAME,
                            "traffic": "short", "chips": 1, "why": "test"}],
             "end_to_end": real["end_to_end"],
             "per_layer": [{"name": n, **layer} for n in READERS]}
    for m in bench["end_to_end"]:
        m.pop("workloads", None)
    return bench


def test_a_new_configuration_runs_by_new_files_alone(tmp_path):
    bench = _bench_dir(tmp_path)
    res = run_cell(f"{NAME}.short", seed=2**33 + 5, seconds=2.5, trace=True,
                   t_proc=time.time(), bench=bench, bench_dir=tmp_path,
                   require_accelerator=False, out=io.StringIO(),
                   err=io.StringIO())
    assert res["correct"], res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == set(READERS)
    # its own counts: no prefill FLOPs, 3 a decoded token
    assert got["flops_counted"] > 0 and got["flops_counted"] % 3.0 == 0
    # its least bytes read each traced tick's counter change; they add up
    # to the change over the slice that its reader reads
    assert got["decode_tokens_traced"] > 0
    assert got["least_bytes_traced"] == 1000.0 * got["decode_tokens_traced"]
