"""Readers of program counters, on records made by hand."""
import pytest

from conftest import BENCH
from harness import spec


def _reader(name):
    return spec.load_module(BENCH / "metrics" / f"{name}.py", "m_" + name)


def test_snapshot_mb_reads_the_slices_counter_change():
    read = _reader("snapshot_mb").read
    start = {"serve_bytes_total{kind=snapshot}": 9e6,
             "serve_events_total{kind=snapshot}": 3.0, "snapshots": 3.0}
    stop = {"serve_bytes_total{kind=snapshot}": 33e6,
            "serve_events_total{kind=snapshot}": 8.0, "snapshots": 8.0}
    rec = {"trace": {"counters_start": start, "counters_stop": stop}}
    assert read(rec) == pytest.approx(24e6 / 5 / 1e6)
    # no snapshot in the slice, or an untraced run: nothing to read
    assert read({"trace": {"counters_start": stop,
                           "counters_stop": stop}}) is None
    assert read({"trace": {"counters_start": {}, "counters_stop": {}}}) \
        is None
    assert read({}) is None
    # the series appear with the first snapshot: absent counts as 0
    rec = {"trace": {"counters_start": {}, "counters_stop": stop}}
    assert read(rec) == pytest.approx(33e6 / 8 / 1e6)
