"""The launchers' own code on the CPU, under the mesh the chip runs use.

A ``(1, 1)`` mesh from ``make_mesh`` carries ``Auto`` axes; with the
``Explicit`` axes that ``jax.make_mesh`` defaults to, the first
``constrain`` in the embedding raised and neither launcher could start.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import serve, train
from repro.launch.mesh import make_mesh


def test_make_mesh_is_auto_and_data_by_model():
    mesh = make_mesh(1)
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (1, 1)
    assert all(t.name == "Auto" for t in mesh.axis_types)


def test_make_mesh_never_falls_back_to_fewer_chips():
    with pytest.raises(RuntimeError, match="needs"):
        make_mesh(len(jax.devices()) + 1)


@pytest.mark.parametrize("env", ["none", "normal"])
def test_serve_launcher_tiny_matches_static_reference(env):
    args = serve.build_parser().parse_args(
        ["--arch", "olmo-1b", "--tiny", "--requests", "4", "--env", env,
         "--verify-static"])
    cfg = get_config(args.arch, tiny=True)
    run = serve.continuous_main(cfg, make_mesh(1), args)
    assert int(run.summary["completed"]) == len(run.requests)
    for r in run.requests:
        assert run.engine.output(r.rid) == run.reference[r.rid], r.rid


def test_serve_launcher_float32_is_token_exact_at_near_ties():
    """The smoke's traffic (prompts up to 512, 2 workers x 4 slots, worker
    failures).  In bf16 one request's greedy token flips at a near-tie
    between the engine's padded, batched shapes and the static reference's;
    in float32, with the slot cache in the compute dtype, every request is
    token-exact."""
    args = serve.build_parser().parse_args(
        ["--arch", "olmo-1b", "--tiny", "--requests", "8", "--prompt-len",
         "512", "--min-prompt-len", "64", "--new-tokens", "32", "--workers",
         "2", "--slots-per-worker", "4", "--policy", "crch", "--env",
         "normal", "--seed", "5", "--verify-static"])
    cfg = dataclasses.replace(get_config(args.arch, tiny=True),
                              compute_dtype="float32")
    run = serve.continuous_main(cfg, make_mesh(1), args)  # asserts parity
    assert run.engine.cache["k"].dtype == np.float32
    assert int(run.summary["restores"]) > 0


def test_train_launcher_tiny_steps(tmp_path):
    args = train.build_parser().parse_args(
        ["--arch", "olmo-1b", "--tiny", "--steps", "3", "--global-batch",
         "2", "--seq-len", "32", "--ckpt-dir", str(tmp_path)])
    cfg = get_config(args.arch, tiny=True)
    report = train.train_main(cfg, make_mesh(1), args)
    assert report.steps_completed == 3
    assert np.isfinite(report.losses).all()
