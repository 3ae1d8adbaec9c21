"""Every program counter reaches the readers: ``System.counters`` keeps
the ten counters the harness has always read, and adds each series of
the engine's metrics registry under ``family{label=value,...}``."""
import sys
import types

from conftest import BENCH
from harness import adapter

sys.path.insert(0, str(BENCH.parent / "src"))
from repro.obs.metrics import MetricsRegistry  # noqa: E402
from repro.serve import ServeMetrics  # noqa: E402

LEGACY = {"prefill_tokens", "decode_tokens", "usage_tokens",
          "useful_tokens", "failures", "resubmissions", "restores",
          "snapshots", "shed", "rejected"}


def _system(metrics):
    s = adapter.System.__new__(adapter.System)
    s.engine = types.SimpleNamespace(metrics=metrics)
    return s


def test_legacy_counters_unchanged_and_registry_series_present():
    m = ServeMetrics()
    m.prefill_tokens += 1024
    m.decode_tokens += 77
    m.snapshot_overhead_tokens += 3
    m.failures += 2
    m.resubmissions += 1
    m.restores += 1
    m.snapshots += 5
    m.snapshot_deltas += 4
    m.snapshot_bytes += 4.5e6
    m.rejected_on_arrival += 1
    got = _system(m).counters()
    legacy = {k: got[k] for k in LEGACY}
    assert legacy == {"prefill_tokens": 1024.0, "decode_tokens": 77.0,
                      "usage_tokens": 1104.0, "useful_tokens": 0.0,
                      "failures": 2.0, "resubmissions": 1.0,
                      "restores": 1.0, "snapshots": 5.0, "shed": 0.0,
                      "rejected": 1.0}
    assert all(type(v) is float for v in got.values())
    series = {k: v for k, v in got.items() if k not in LEGACY}
    assert series == {
        "serve_tokens_total{kind=prefill}": 1024.0,
        "serve_tokens_total{kind=decode}": 77.0,
        "serve_tokens_total{kind=snapshot_overhead}": 3.0,
        "serve_events_total{kind=worker_failure}": 2.0,
        "serve_events_total{kind=resubmission}": 1.0,
        "serve_events_total{kind=snapshot_restore}": 1.0,
        "serve_events_total{kind=snapshot}": 5.0,
        "serve_events_total{kind=snapshot_delta}": 4.0,
        "serve_bytes_total{kind=snapshot}": 4.5e6,
        "serve_drops_total{reason=rejected_on_arrival}": 1.0}


def test_a_family_registered_later_reaches_the_readers():
    m = ServeMetrics()
    m.registry.counter("serve_experts_total", "", ("kind", "layer")).inc(
        6, kind="hit", layer="3")
    m.registry.gauge("serve_live_slots").set(12)
    m.registry.histogram("serve_tick_seconds", buckets=(0.1,)).observe(0.05)
    got = _system(m).counters()
    assert got["serve_experts_total{kind=hit,layer=3}"] == 6.0
    assert got["serve_live_slots"] == 12.0
    assert got["serve_tick_seconds_count"] == 1.0
    assert got["serve_tick_seconds_sum"] == 0.05


def test_registry_series_of_an_empty_registry():
    assert adapter.registry_series(MetricsRegistry()) == {}
