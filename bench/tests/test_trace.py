"""The trace reducer, on a synthetic trace and on a recorded one (two
ticks of ``olmo-1b.chat`` on one TPU v5e chip, cut from a chip run)."""
import gzip
import json
from pathlib import Path

import pytest

from harness import trace

DATA = Path(__file__).parent / "data" / "trace-olmo-1b.json.gz"


def test_synthetic_trace():
    ev = {"host": [["bench.submit", 0, 10], ["bench.step", 10, 100],
                   ["bench.observe", 110, 10]],
          "modules": [["jit_prefill_last_idx_step(1)", 20, 30],
                      ["jit_serve_step_masked(2)", 60, 40]],
          "ops": [["%a", 20, 10], ["%b", 25, 25], ["%c", 60, 40]]}
    r = trace.reduce(ev)
    assert r["slice_s"] == pytest.approx(120e-9)
    assert r["busy_s"] == pytest.approx(70e-9)
    assert r["programs"]["serve_step_masked"] == {"count": 1,
                                                  "device_s": 40e-9}
    gaps = r["breakdown"]["idle_gaps"]
    # idle [0, 20], [100, 120], [50, 60], longest first; each tagged by
    # the innermost annotation open at its middle
    assert [g[0] for g in gaps] == ["bench.step", "bench.observe",
                                    "bench.step"]
    assert [g[1] for g in gaps] == pytest.approx([20e-9, 20e-9, 10e-9])
    assert r["breakdown"]["device_ops"][0] == ["serve_step_masked", 40e-9]


def test_program_name():
    assert trace.program_name("jit_serve_step_masked(9242564)") == \
        "serve_step_masked"
    assert trace.program_name("jit__lambda(1)") == "_lambda"


def test_recorded_trace():
    with gzip.open(DATA, "rt") as f:
        ev = json.load(f)
    r = trace.reduce(ev, top=10_000)
    lo = min(s for _, s, _ in ev["host"])
    hi = max(s + d for _, s, d in ev["host"])
    assert r["slice_s"] == pytest.approx((hi - lo) * 1e-9)
    steps = [e for e in ev["host"] if e[0] == "bench.step"]
    assert r["programs"]["serve_step_masked"]["count"] == len(steps) == 2
    mod_s = sum(d for _, s, d in ev["modules"]) * 1e-9
    # ops run inside the programs: busy is at most the programs' time and
    # covers nearly all of it
    assert 0.97 * mod_s <= r["busy_s"] <= mod_s + 1e-9
    idle = sum(g[1] for g in r["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(r["slice_s"] - r["busy_s"], rel=1e-6)
    assert {g[0] for g in r["breakdown"]["idle_gaps"]} <= {
        "bench.submit", "bench.step", "bench.observe", "none"}
    top = r["breakdown"]["device_ops"]
    assert top == sorted(top, key=lambda x: -x[1])
    assert {n for n, _ in top} == {
        "serve_step_masked", "prefill_last_idx_step", "_lambda",
        "convert_element_type", "dynamic_slice", "squeeze"}
    assert sum(s for _, s in top) == pytest.approx(mod_s)


# ``reduce``'s outputs before it gave ``op_seconds``, on both recorded
# traces, at the run's ``top`` and at one that keeps everything
GOLDEN = Path(__file__).parent / "data" / "reduce-golden.json"


@pytest.mark.parametrize("config", ["deepseek-coder-33b", "olmo-1b"])
def test_op_seconds_beside_the_old_outputs(config):
    with gzip.open(Path(__file__).parent / "data" /
                   f"trace-{config}.json.gz", "rt") as f:
        ev = json.load(f)
    golden = json.loads(GOLDEN.read_text())[config]
    for key, top in (("top10", 10), ("all", 10_000)):
        r = trace.reduce(ev, top=top)
        ops = r.pop("op_seconds")
        assert json.dumps(r, sort_keys=True) == \
            json.dumps(golden[key], sort_keys=True)
    lo = min(s for _, s, _ in ev["host"])
    hi = max(s + d for _, s, d in ev["host"])
    want = {}
    for name, s, d in ev["ops"]:
        if lo <= s and s + d <= hi:
            want[name] = want.get(name, 0.0) + d * 1e-9
    assert ops == pytest.approx(want, rel=1e-12) and len(ops) > 100
    assert all(n.startswith("%") for n in ops)
    # ops nest (a loop holds its body's ops): each one alone fits in the
    # busy time, all of them together need not
    assert 0 < max(ops.values()) <= r["busy_s"] + 1e-12


def test_op_seconds_synthetic():
    ev = {"host": [["bench.step", 10, 100]],
          "modules": [["jit_serve_step_masked(2)", 20, 80]],
          "ops": [["%while.1", 20, 60], ["%fusion.2", 25, 10],
                  ["%fusion.2", 40, 10], ["%custom-call.3", 85, 10],
                  ["%early", 0, 15], ["%late", 105, 10]]}
    r = trace.reduce(ev)
    assert r["op_seconds"] == pytest.approx(
        {"%while.1": 60e-9, "%fusion.2": 20e-9, "%custom-call.3": 10e-9})
