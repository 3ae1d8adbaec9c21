"""``chip_smoke.py``'s phases at the tiny config on the CPU.

The script's own entry point refuses any platform but the TPU; its phase
functions take the config, mesh and interpret flag from the caller, so the
same checks run here at a size the CPU handles.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_entry_point_refuses_a_platform_without_tpu(smoke):
    with pytest.raises(SystemExit, match="needs a TPU.*'cpu'"):
        smoke.main([])


def test_serve_phase_tiny(smoke):
    cfg = get_config("olmo-1b", tiny=True)
    mesh = make_mesh(1)
    run, par = smoke.serve_phase(cfg, mesh)  # completes, restores, parity
    assert run.summary["restores"] > 0
    # every token the engine delivered, and the prefill's, has its row
    assert par["rows"] >= sum(r.max_new_tokens for r in run.requests)
    assert par["max_abs"] <= smoke.SAME_DTYPE_BOUND[0]
    cmp = smoke.serve_vs_host(cfg, run, mesh)
    assert cmp["max_abs"] <= smoke.HOST_F32_BOUND[0]


def test_float32_phase_tiny_is_token_exact(smoke):
    cfg = get_config("olmo-1b", tiny=True)
    run = smoke.float32_phase(cfg, make_mesh(1))   # checks token parity
    assert run.engine.cache["k"].dtype == np.float32
    assert run.summary["restores"] > 0


def test_scheduler_phase_interpreted(smoke):
    sch = smoke.scheduler_phase(200, interpret=True)
    assert sch["max_abs"] <= smoke.DIST_ATOL
    assert not sch["compiled_kernel"]        # interpreted: no TPU kernel


def test_four_chip_phase_on_virtual_devices():
    """``--chips 4``'s path on four virtual CPU devices, in a child process
    of its own (the device count is fixed when JAX starts)."""
    code = (
        "import chip_smoke as s\n"
        "from repro.configs import get_config\n"
        "from repro.launch.mesh import make_mesh\n"
        "cfg = get_config('olmo-1b', tiny=True)\n"
        "mesh = make_mesh(4)\n"
        "run, _ = s.serve_phase(cfg, mesh)\n"
        "print('worst', s.sharded_vs_one_chip(cfg, run, mesh))\n"
        "s.float32_phase(cfg, mesh)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "cache k" in out.stdout and "4 devices" in out.stdout
    assert "worst" in out.stdout
