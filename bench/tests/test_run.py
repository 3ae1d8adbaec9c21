"""Whole runs on the CPU at a tiny size, past the harness's look for a
chip: a sound run is correct, and each fault the cells can have makes
``correct`` false.  Also: ``run.py`` refuses to run without a TPU."""
import io
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH, CONFIGS, limit_of
from harness.cellrun import run_cell

sys.path.insert(0, str(BENCH.parent / "src"))
from repro.distributed.steps import make_serve_step  # noqa: E402


def _run(tiny_bench, config, break_path=None, seed=2**31 + 17,
         control=False):
    bench, root = tiny_bench(config, limit=limit_of(config))
    out, err = io.StringIO(), io.StringIO()
    res = run_cell(f"{config}.tiny", seed=seed, seconds=2.5, trace=False,
                   t_proc=time.time(), bench=bench, bench_dir=root,
                   require_accelerator=False, break_path=break_path,
                   control=control, out=out, err=err)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line == res and list(line)[-1] == "checks"
    assert err.getvalue().strip().splitlines()[-1].startswith(
        "check tokens_compared")
    return res


def _stale_cache(system):
    """A decode step that returns its cache unchanged."""
    e = system.engine
    step = jax.jit(make_serve_step(e.cfg, cache_axes=e.axes))

    def serve(params, cache, toks, pos, live):
        nxt, logits, _ = step(params, cache, toks, pos, live)
        return nxt, logits, cache
    e._serve = serve


def _altered_token(system):
    """A decode step whose token is altered where it is produced."""
    e = system.engine
    serve0 = e._serve
    vocab = e.cfg.vocab_size

    def serve(params, cache, toks, pos, live):
        nxt, logits, cache = serve0(params, cache, toks, pos, live)
        return (nxt + 1) % vocab, logits, cache
    e._serve = serve


@pytest.mark.parametrize("config", CONFIGS)
def test_sound_run_is_correct(tiny_bench, config):
    res = _run(tiny_bench, config)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"delivered_tok_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", [_stale_cache, _altered_token])
@pytest.mark.parametrize("config", CONFIGS)
def test_fault_makes_the_run_incorrect(tiny_bench, config, fault):
    res = _run(tiny_bench, config, break_path=fault)
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > limit_of(config)


@pytest.mark.parametrize("config", CONFIGS)
def test_control_in_the_programs_place_is_incorrect(tiny_bench, config):
    res = _run(tiny_bench, config, control=True)
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > limit_of(config)


@pytest.mark.parametrize("config", CONFIGS)
def test_traced_run_reports_per_layer_metrics(tiny_bench, config):
    bench, root = tiny_bench(config, limit=limit_of(config))
    out = io.StringIO()
    res = run_cell(f"{config}.tiny", seed=5, seconds=2.5, trace=True,
                   t_proc=time.time(), bench=bench, bench_dir=root,
                   require_accelerator=False, out=out, err=io.StringIO())
    # the CPU has no device plane: only the counter-based metrics read
    assert {"serve_mfu", "waste_share"} <= set(res["metrics"])
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "deepseek-coder-33b.chat-saturated",
         "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_no_result():
    p = _cli(BENCH.parent)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
