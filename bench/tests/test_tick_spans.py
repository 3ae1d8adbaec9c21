"""The tick-span tool (``tools/tick_spans.py``): its reduction on a
synthetic trace and on a recorded one (two ticks of
``deepseek-coder-33b.chat-saturated`` with two decode snapshots, on one
TPU v5e chip, cut from a chip run), its reading of a profile, and
one run on the CPU.  The harness's own reducer reads the recorded trace as
it reads one without the program's spans."""
import gzip
import importlib.util
import json
from pathlib import Path

import pytest

from harness import trace

BENCH = Path(__file__).resolve().parents[1]
DEEPSEEK = BENCH / "tests" / "data" / "trace-deepseek-coder-33b.json.gz"

_spec = importlib.util.spec_from_file_location(
    "tick_spans", BENCH / "tools" / "tick_spans.py")
tick_spans = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tick_spans)

# one tick under bench.step (10-200): faults, admit with a prefill and its
# wait, decode and its wait, a snapshot copy and digest
PROGRAM = [["serve.tick", 12, 186],
           ["serve.tick.faults", 13, 2], ["serve.tick.shed", 15, 1],
           ["serve.tick.admit", 16, 44], ["serve.prefill", 17, 42],
           ["serve.prefill.wait", 30, 28], ["serve.decode", 60, 50],
           ["serve.decode.wait", 70, 38], ["serve.tick.snapshots", 110, 87],
           ["serve.snapshot.take", 111, 85],
           ["serve.snapshot.copy", 112, 40],
           ["serve.snapshot.digest", 152, 43]]
STEP_EVENTS = {
    "host": [["bench.submit", 0, 10], ["bench.step", 10, 190],
             ["bench.observe", 200, 20]],
    "modules": [["jit_prefill_last_idx_step(1)", 20, 35],
                ["jit_serve_step_masked(2)", 65, 40],
                ["jit_slot_read(3)", 112, 5]],
    "ops": [["%a", 20, 35], ["%b", 65, 40], ["%c", 112, 5]]}


def test_program_spans_tag_the_idle_time():
    r = tick_spans.where(dict(STEP_EVENTS, program=PROGRAM))
    bare = tick_spans.where(dict(STEP_EVENTS, program=[]))
    assert r["slice_s"] == pytest.approx(220e-9)
    assert r["idle_s"] == pytest.approx(140e-9)
    # idle [0, 20], [55, 65], [105, 112], [117, 220]: each tagged by the
    # innermost span open at its middle
    gaps = r["idle_gaps"]
    assert gaps[0] == ["serve.snapshot.digest", pytest.approx(103e-9)]
    assert [g[0] for g in gaps[1:]] == ["bench.step", "serve.decode",
                                        "serve.decode"]
    assert r["idle_by_span"] == pytest.approx({
        "bench.submit": 10e-9, "bench.step": 4e-9, "serve.tick": 2e-9,
        "serve.tick.faults": 2e-9, "serve.tick.shed": 1e-9,
        "serve.tick.admit": 2e-9, "serve.prefill": 4e-9,
        "serve.prefill.wait": 3e-9, "serve.decode": 7e-9,
        "serve.decode.wait": 3e-9, "serve.tick.snapshots": 2e-9,
        "serve.snapshot.take": 2e-9, "serve.snapshot.copy": 35e-9,
        "serve.snapshot.digest": 43e-9, "bench.observe": 20e-9})
    assert r["snapshot_idle_share"] == pytest.approx(100 * 80 / 140)
    assert r["snapshot_ms"] == pytest.approx(85e-6)
    # the tick less the time its two waits held the host
    assert r["tick_host_ms"] == pytest.approx((186 - 66) * 1e-6)
    assert r["tick_cover_min"] == pytest.approx(
        (2 + 1 + 44 + 50 + 87) / 186)
    assert r["ticks"] == 1
    # without the program's spans the same time goes to the harness's
    assert bare["idle_by_span"] == pytest.approx({
        "bench.submit": 10e-9, "bench.step": 110e-9,
        "bench.observe": 20e-9})
    assert bare["snapshot_ms"] is None and bare["tick_host_ms"] is None
    assert bare["ticks"] == 0


def test_innermost_cuts_an_overlong_child():
    segs = tick_spans.innermost([["a", 0, 10], ["b", 5, 20]], 0, 30)
    assert segs == [(0, 5, "a"), (5, 10, "b"), (10, 30, "none")]


def test_program_events_come_from_the_annotate_sink(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.obs.trace import Tracer
    rec = type("R", (), {"record": lambda self, r: None})()
    tr = Tracer(rec, annotate=jax.profiler.TraceAnnotation)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        with tr.span("serve.tick"):
            with tr.span("serve.decode"):
                jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.extract(str(tmp_path))
    program = tick_spans.program_events(str(tmp_path))
    # the harness keeps its own annotations only
    assert [e[0] for e in ev["host"]] == ["bench.step"]
    assert sorted(e[0] for e in program) == ["serve.decode", "serve.tick"]
    (_, s0, d0), = ev["host"]
    assert all(s0 <= s and s + d <= s0 + d0 for _, s, d in program)


def test_recorded_trace_with_program_spans():
    with gzip.open(DEEPSEEK, "rt") as f:
        ev = json.load(f)
    # the harness's reducer leaves the program's spans out
    r = trace.reduce(ev)
    bare = trace.reduce({k: ev[k] for k in ("modules", "ops", "host")})
    assert r == bare
    # the slot programs have names of their own
    names = {n for n, _ in trace.reduce(ev, top=100)["breakdown"]
             ["device_ops"]}
    assert {"serve_step_masked", "prefill_last_idx_step", "slot_read",
            "cache_insert"} <= names and "_lambda" not in names
    w = tick_spans.where(ev, top=10_000)
    idle = r["slice_s"] - r["busy_s"]
    assert w["slice_s"] == pytest.approx(r["slice_s"])
    assert w["idle_s"] == pytest.approx(idle, rel=1e-6)
    assert sum(g[1] for g in w["idle_gaps"]) == pytest.approx(idle,
                                                               rel=1e-6)
    # the snapshots hold the device idle, and the harness's step is left
    # with almost none of it
    by = w["idle_by_span"]
    assert w["snapshot_idle_share"] > 75
    assert by.get("bench.step", 0.0) < 0.01 * idle
    assert w["idle_gaps"][0][0].startswith("serve.snapshot.")
    # two ticks, each covered by its phases, each with a snapshot
    assert w["ticks"] == 2 and w["tick_cover_min"] >= 0.95
    assert 0 < w["tick_host_ms"] < 1e3 * r["slice_s"] / 2
    assert w["snapshot_ms"] > 0


def test_tool_runs_on_the_cpu(tiny_bench):
    # the CPU has no device plane: the program's spans are read, the
    # device's idle time is the whole slice
    bench, root = tiny_bench("deepseek-coder-33b")
    out = tick_spans.run("deepseek-coder-33b.tiny", seed=5, seconds=2.5,
                         bench=bench, bench_dir=root,
                         require_accelerator=False)
    json.dumps(out)
    assert out["ticks"] > 0 and out["tick_host_ms"] > 0
    assert out["tick_cover_min"] >= 0.5
    assert out["idle_s"] == pytest.approx(out["slice_s"])
    assert out["queue_wait_s"] is None or out["queue_wait_s"] >= 0
    assert out["tick_ms_in"] > 0 and out["tick_ms_out"] > 0
