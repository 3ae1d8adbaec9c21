"""Tests for repro.obs: tracer/recorder semantics, metrics exposition,
dump-on-fault through a real chaos coordinator run, and the two invariants
the instrumented layers promise:

* a disabled tracer is a strict no-op (shared null span, no records);
* tracing is *passive* — a chaos-matrix cell replayed with the flight
  recorder attached produces a byte-identical result row.
"""
import collections
import json

import jax
import numpy as np
import pytest

from repro.chaos import (CKPT_CORRUPT, HOST_CRASH, NAN_POISON, SLOWDOWN,
                         ChaosEngine, FaultEvent, FaultTrace)
from repro.configs import get_config
from repro.data import DataConfig, SyntheticTokenPipeline
from repro.distributed.steps import make_train_step
from repro.ft import (CheckpointStore, DynamicInterval, TrainingCoordinator)
from repro.ft.crosspod import PodGradientExchange
from repro.models import lm
from repro.obs import (NULL_TRACER, FlightRecorder, MetricsRegistry, Tracer,
                       load_jsonl, profile_jit, setup, to_chrome)
from repro.obs.validate import validate_chrome, validate_dir, validate_events
from repro.optim import adamw_init
from repro.serve.metrics import ServeMetrics


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


# ------------------------------------------------------------- tracer ----

def test_null_tracer_is_shared_noop():
    assert not NULL_TRACER.enabled
    s1 = NULL_TRACER.span("x", step=1)
    s2 = NULL_TRACER.span("y")
    assert s1 is s2                       # one cached null object, no alloc
    with s1 as sp:
        assert sp.set(a=1) is sp
    NULL_TRACER.event("e")
    NULL_TRACER.fault("host_crash", step=3)
    NULL_TRACER.recovery("host_crash")
    # a tracer without a recorder is disabled even when asked to enable
    assert not Tracer(None, enabled=True).enabled


def test_span_nesting_parent_ids_and_error_attr():
    rec = FlightRecorder(64, clock=FakeClock())
    tr = Tracer(rec, clock=FakeClock())
    with tr.span("outer", step=1) as outer:
        with tr.span("inner"):
            tr.event("tick", n=2)
        outer.set(result="ok")
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    events = rec.snapshot()
    by_name = {e["name"]: e for e in events}
    assert by_name["tick"]["parent_id"] == by_name["inner"]["span_id"]
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["outer"]["parent_id"] is None
    assert by_name["outer"]["attrs"] == {"step": 1, "result": "ok"}
    assert by_name["boom"]["attrs"]["error"] == "RuntimeError"
    # inner spans close first -> emitted first
    names = [e["name"] for e in events]
    assert names.index("inner") < names.index("outer")
    assert validate_events(events) == []


class FakeAnnotation:
    """Context-manager factory that logs each enter and exit by name."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))
                return False
        return _Ann()


def test_annotate_sink_follows_span_names_and_nesting():
    ann = FakeAnnotation()
    rec = FlightRecorder(64, clock=FakeClock())
    tr = Tracer(rec, clock=FakeClock(), annotate=ann)
    with tr.span("outer", step=1):
        with tr.span("inner", k=2):
            tr.event("tick")          # point events open no annotation
        with tr.span("second"):
            pass
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert ann.log == [("enter", "outer"), ("enter", "inner"),
                       ("exit", "inner"), ("enter", "second"),
                       ("exit", "second"), ("exit", "outer"),
                       ("enter", "boom"), ("exit", "boom")]
    # the recorder's spans close in the same order; attributes stay there
    spans = [e for e in rec.snapshot() if e["type"] == "span"]
    assert [e["name"] for e in spans] == ["inner", "second", "outer",
                                          "boom"]
    assert spans[0]["attrs"] == {"k": 2}
    # disabled tracers open no span, so never call the factory
    for off in (NULL_TRACER, Tracer(None, annotate=ann),
                Tracer(rec, enabled=False, annotate=ann)):
        with off.span("x"):
            off.event("y")
    assert len(ann.log) == 8


def test_complete_bypasses_stack():
    rec = FlightRecorder(16, clock=FakeClock())
    tr = Tracer(rec, clock=FakeClock())
    with tr.span("live"):
        tr.complete("offthread", 1.0, 5.0, track="ckpt-io", mode="async")
    off = [e for e in rec.snapshot() if e["name"] == "offthread"][0]
    assert off["parent_id"] is None and off["track"] == "ckpt-io"
    assert off["t0"] == 1.0 and off["t1"] == 5.0


# ----------------------------------------------------- recorder / ring ----

def test_ring_evicts_oldest_first():
    rec = FlightRecorder(4, clock=FakeClock())
    tr = Tracer(rec, clock=FakeClock())
    for i in range(10):
        tr.event(f"e{i}")
    assert len(rec) == 4
    assert [e["name"] for e in rec.snapshot()] == ["e6", "e7", "e8", "e9"]


def test_dump_on_fault_labels_cap_and_counters(tmp_path):
    clock = FakeClock()
    rec = FlightRecorder(32, out_dir=str(tmp_path), dump_on_fault=True,
                         max_dumps=3, clock=clock)
    tr = Tracer(rec, clock=clock)
    tr.fault("host_crash", step=1)
    tr.recovery("host_crash", restored_step=0)
    tr.fault("nan poison/..", step=2)     # label must be sanitized
    tr.fault("disk_full", step=3)         # over the cap: counted, not dumped
    assert [p.rsplit("/", 1)[-1] for p in rec.dumps] == [
        "0000_fault_host_crash.jsonl", "0001_recovery_host_crash.jsonl",
        "0002_fault_nan_poison_...jsonl"]
    assert rec.faults_seen == collections.Counter(
        {"host_crash": 1, "nan poison/..": 1, "disk_full": 1})
    assert rec.recoveries_seen == collections.Counter({"host_crash": 1})
    # the explicit final dump ignores the auto-dump cap
    final = rec.dump("run_end")
    assert final.endswith("0003_run_end.jsonl")
    assert [e["name"] for e in load_jsonl(final)] == [
        "fault.host_crash", "recover.host_crash", "fault.nan poison/..",
        "fault.disk_full"]
    problems, summary = validate_dir(str(tmp_path))
    assert problems == [] and summary["jsonl_files"] == 4


def test_window_filters_old_events():
    clock = FakeClock()
    rec = FlightRecorder(100, window_s=3.0, clock=clock)
    tr = Tracer(rec, clock=clock)
    for i in range(8):
        tr.event(f"e{i}")                 # event i lands at t = i + 1
    # snapshot() reads the clock once more; only the last ~3s survive
    assert [e["name"] for e in rec.snapshot()] == ["e5", "e6", "e7"]


def test_chrome_conversion_schema():
    rec = FlightRecorder(16, clock=FakeClock())
    tr = Tracer(rec, clock=FakeClock())
    with tr.span("work", step=4, skip=None):
        tr.event("mark")
    doc = to_chrome(rec.snapshot())
    assert validate_chrome(doc) == []
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    marks = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert len(spans) == 1 and len(marks) == 1
    assert spans[0]["dur"] > 0
    assert "skip" not in spans[0]["args"]     # None attrs are elided


# ------------------------------------------------------------ metrics ----

def test_counter_labels_and_value():
    reg = MetricsRegistry()
    c = reg.counter("drops_total", "drops", ("reason",))
    c.inc(reason="shed")
    c.inc(2.0, reason="hedge")
    assert c.value(reason="shed") == 1.0 and c.total() == 3.0
    assert reg.value("drops_total", reason="hedge") == 2.0
    assert reg.value("missing_metric") == 0.0
    with pytest.raises(ValueError):
        c.inc(wrong="label")
    # re-registration returns the same instrument; kind mismatch raises
    assert reg.counter("drops_total", "drops", ("reason",)) is c
    with pytest.raises(ValueError):
        reg.gauge("drops_total")


def test_prometheus_escaping_and_exposition():
    reg = MetricsRegistry()
    c = reg.counter("odd_total", 'help with \\ and\nnewline', ("path",))
    c.inc(path='a"b\\c\nd')
    text = reg.to_prometheus()
    assert '# HELP odd_total help with \\\\ and\\nnewline' in text
    assert 'odd_total{path="a\\"b\\\\c\\nd"} 1.0' in text
    assert "# TYPE odd_total counter" in text


def test_histogram_exposition_cumulative(tmp_path):
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", ("op",),
                      buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v, op="step")
    text = reg.to_prometheus()
    assert 'lat_seconds_bucket{op="step",le="0.1"} 1' in text
    assert 'lat_seconds_bucket{op="step",le="1.0"} 3' in text
    assert 'lat_seconds_bucket{op="step",le="+Inf"} 4' in text
    assert 'lat_seconds_count{op="step"} 4' in text
    assert h.sum(op="step") == pytest.approx(6.05)
    jpath, ppath = reg.write(str(tmp_path))
    dumped = json.load(open(jpath))
    assert dumped["lat_seconds"]["series"]["op=step"]["count"] == 4


def test_serve_metrics_shim_maps_to_registry():
    reg = MetricsRegistry()
    m = ServeMetrics(registry=reg)
    m.shed += 1
    m.rejected_on_arrival += 2
    m.past_first_token_drops += 1
    m.failures += 1
    m.prefill_tokens += 64
    assert m.shed == 1 and m.rejected_on_arrival == 2
    assert reg.value("serve_drops_total", reason="shed") == 1.0
    assert reg.value("serve_drops_total",
                     reason="rejected_on_arrival") == 2.0
    assert reg.value("serve_drops_total", reason="past_first_token") == 1.0
    assert reg.value("serve_events_total", kind="worker_failure") == 1.0
    assert reg.value("serve_tokens_total", kind="prefill") == 64.0
    s = m.summary(10)
    assert s["shed"] == 1 and s["past_first_drops"] == 1


# ------------------------------------------------------------ profile ----

def test_profile_jit_records_compile_then_steady_state():
    reg = MetricsRegistry()
    fn = jax.jit(lambda x: x * 2.0)
    prof = profile_jit(fn, name="double", registry=reg, clock=FakeClock())
    x = np.ones(4, np.float32)
    for _ in range(4):
        prof(x)
    rep = prof.report()
    assert rep["compile_s"] is not None and rep["calls"] == 3
    assert reg.value("profile_compile_seconds", step="double") > 0
    assert reg.value("profile_step_seconds", step="double") == 3.0
    cost = prof.capture_cost(x)
    assert prof.stats.flops is not None and "flops" in cost
    assert prof.report()["achieved_flops_per_s"] is not None


# ----------------------------------------- chaos run -> dumps on fault ----

@pytest.fixture(scope="module")
def train_setup():
    cfg = get_config("olmo-1b", tiny=True)
    params = lm.init_params(jax.random.key(0), cfg)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, q_chunk=16, xent_chunk=16))
    data_cfg = DataConfig(global_batch=4, seq_len=32)
    return cfg, params, opt, step, data_cfg


def run_chaos_coordinator(train_setup, ckpt_dir, *, tracer=None,
                          registry=None, n_steps=18):
    cfg, params, opt, step, data_cfg = train_setup
    trace = FaultTrace(events=[
        FaultEvent(step=3, kind=SLOWDOWN, targets=(0,), duration=2),
        FaultEvent(step=6, kind=NAN_POISON),
        FaultEvent(step=9, kind=CKPT_CORRUPT, targets=(0,)),
        FaultEvent(step=11, kind=HOST_CRASH, targets=(0,), duration=2),
    ])
    coord = TrainingCoordinator(
        train_step=step, params=params, opt_state=opt,
        pipeline=SyntheticTokenPipeline(data_cfg, cfg),
        store=CheckpointStore(ckpt_dir, tracer=tracer),
        interval=DynamicInterval(gamma_s=1.0, lam_min=2.0, lam_max=2.0),
        chaos=ChaosEngine(trace, tracer=tracer),
        tracer=tracer, registry=registry)
    return coord.run(n_steps)


def test_coordinator_dumps_on_three_fault_classes(train_setup, tmp_path):
    ctx = setup(str(tmp_path / "trace"), dump_on_fault=True)
    report = run_chaos_coordinator(train_setup, str(tmp_path / "ckpt"),
                                   tracer=ctx.tracer, registry=ctx.registry)
    assert report.steps_completed == 18
    assert ctx.finish() is not None
    assert set(ctx.recorder.faults_seen) >= {
        SLOWDOWN, NAN_POISON, CKPT_CORRUPT, HOST_CRASH}
    dump_names = [p.rsplit("/", 1)[-1] for p in ctx.recorder.dumps]
    for kind in (SLOWDOWN, NAN_POISON, CKPT_CORRUPT, HOST_CRASH):
        assert any(f"fault_{kind}" in n for n in dump_names), kind
    problems, summary = validate_dir(
        str(tmp_path / "trace"),
        require_spans=[f"fault.{HOST_CRASH}", f"recover.{HOST_CRASH}",
                       f"recover.{NAN_POISON}", "ckpt.save",
                       "ckpt.restore"])
    assert problems == []
    # the registry absorbed the coordinator's counters
    assert ctx.registry.value("train_events_total", kind="failure") >= 1
    assert ctx.registry.value("train_events_total",
                              kind="nan_rollback") >= 1
    assert ctx.registry.value("train_checkpoints_total",
                              mode="sync") + ctx.registry.value(
        "train_checkpoints_total", mode="async") == report.checkpoints


def test_traced_run_is_bit_identical_to_untraced(train_setup, tmp_path):
    plain = run_chaos_coordinator(train_setup, str(tmp_path / "a"))
    ctx = setup(str(tmp_path / "trace"), dump_on_fault=True)
    traced = run_chaos_coordinator(train_setup, str(tmp_path / "b"),
                                   tracer=ctx.tracer,
                                   registry=ctx.registry)
    assert plain.losses == traced.losses
    assert plain.failures == traced.failures
    assert plain.nan_rollbacks == traced.nan_rollbacks
    assert plain.checkpoints == traced.checkpoints


def test_chaos_matrix_serve_cell_row_identical_traced(tmp_path):
    chaos_matrix = pytest.importorskip(
        "benchmarks.chaos_matrix",
        reason="benchmarks/ not importable from this rootdir")
    cfg = get_config("olmo-1b", tiny=True)
    # the serving weights, as run_matrix gives every serve cell's engine
    params = lm.serving_params(lm.init_params(jax.random.key(1), cfg), cfg)
    trace = chaos_matrix.cell_trace("unstable", "serve", HOST_CRASH,
                                    horizon=120, n_targets=4, seed=5)
    kw = dict(n_requests=4, max_steps=400, seed=5)
    # ChaosEngine never mutates the trace, so the same one replays twice
    row_plain = chaos_matrix.run_serve_cell(cfg, params, trace, **kw)
    ctx = setup(str(tmp_path / "trace"), dump_on_fault=True)
    row_traced = chaos_matrix.run_serve_cell(cfg, params, trace,
                                             tracer=ctx.tracer, **kw)
    assert (json.dumps(row_plain, sort_keys=True)
            == json.dumps(row_traced, sort_keys=True))
    assert ctx.recorder.faults_seen


# ------------------------------------------------- fingerprint gating ----

def test_exchange_round_skips_fingerprint_on_request():
    ex = PodGradientExchange(2)
    grads = {"w": np.ones(8, np.float32)}
    with_fp = ex.round([grads, grads])
    assert with_fp.fingerprint
    without = ex.round([grads, grads], with_fingerprint=False)
    assert without.fingerprint is None
