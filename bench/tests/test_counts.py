"""Counts by configuration: a configuration whose module brings no counts
gets harness/counts.py's dense ones, bit for bit as the record always
had them; one that brings its own gets those, its least bytes fed the
program counters' change over each traced tick."""
import json
import types

import pytest

from conftest import BENCH
from harness import cellrun, counts, spec
from harness.window import Tick

CONFIG = "deepseek-coder-33b"


def _m():
    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    return dict(cfg["model"], **cfg["reference"])


def _ticks():
    """Fixed ticks: prefills of (true, padded) lengths, decoded slots by
    the positions they attend, some ticks with no decode."""
    return [Tick(0.0, 0.1, [], [(1020, 1024)], 0, 0),
            Tick(0.1, 0.2, [1021], [(17, 32), (4096, 4096)], 1, 0),
            Tick(0.2, 0.3, [1022, 18, 4097], [], 2, 1),
            Tick(0.3, 0.4, [], [], 0, 0),
            Tick(0.4, 0.5, [1023, 19, 4098, 7, 4600] + [300] * 7,
                 [(129, 256)], 3, 0),
            Tick(0.5, 0.6, [1024, 20], [], 0, 0)]


def _record(tally, m, ticks, prof):
    res = types.SimpleNamespace(
        ticks=ticks, clients={}, t1=0.6, seconds=0.6, delivered=9,
        counters_mid={}, counters_end={})
    cell = types.SimpleNamespace(chips=1, bench_dir=BENCH)
    reduced = {"slice_s": 0.5, "busy_s": 0.4, "programs": {},
               "op_seconds": {}, "breakdown": {}}
    return cellrun._record(cell, tally, m, res, 12.0, {"kind": "TPU v5 lite"},
                           reduced, prof)


def _prof(k0, k1, at):
    prof = cellrun._Profiler(0.0, 1.0, None, counters=dict)
    prof.k0, prof.k1, prof.at = k0, k1, at
    return prof


def test_dense_counts_are_the_old_sums():
    ref = spec.load_module(BENCH / "configs" / f"{CONFIG}.py", "ref_c")
    tally = counts.Counts(ref)
    assert not tally.per_tick_counters
    m, ticks = _m(), _ticks()
    prof = _prof(1, 5, {1: {"snapshots": 3.0}, 5: {"snapshots": 9.0}})
    rec = _record(tally, m, ticks, prof)
    # the record's sums as the harness took them before counts could come
    # from a configuration's module
    flops = 0.0
    for t in ticks:
        flops += sum(counts.prefill_flops(m, p) for p, _ in t.prefills)
        flops += sum(counts.decode_flops(m, a) for a in t.decoded)
    least = sum(counts.decode_least_bytes(m, t.decoded)
                for t in ticks[1:5] if t.decoded)
    assert rec["flops"] == flops and flops > 0
    assert rec["trace"]["decode_least_bytes"] == least and least > 0
    assert rec["trace"]["decode_ticks"] == 3
    assert rec["trace"]["prefill_padded_tokens"] == 4096 + 32 + 256
    assert rec["trace"]["counters_start"] == {"snapshots": 3.0}
    assert rec["trace"]["counters_stop"] == {"snapshots": 9.0}


def test_a_configurations_own_counts_replace_the_dense_ones():
    seen = []

    def decode_least_bytes(m, attended, counters):
        seen.append(counters)
        return 1000.0 * counters["experts_hit"] + len(attended)

    own = types.SimpleNamespace(decode_flops=lambda m, a: 3.0,
                                decode_least_bytes=decode_least_bytes)
    tally = counts.Counts(own)
    assert tally.per_tick_counters
    assert tally.prefill_flops is counts.prefill_flops   # not brought
    m, ticks = _m(), _ticks()
    # counters before ticks 1..4 and after tick 4; a series that appears
    # inside the slice counts from 0
    at = {1: {"experts_hit": 2.0}, 2: {"experts_hit": 5.0},
          3: {"experts_hit": 5.0, "new{kind=x}": 1.0},
          4: {"experts_hit": 6.0, "new{kind=x}": 1.0},
          5: {"experts_hit": 10.0, "new{kind=x}": 4.0}}
    rec = _record(tally, m, ticks, _prof(1, 5, at))
    prefill = sum(counts.prefill_flops(m, p) for t in ticks
                  for p, _ in t.prefills)
    decoded = sum(len(t.decoded) for t in ticks)
    assert rec["flops"] == prefill + 3.0 * decoded
    # ticks 1, 2 and 4 decode: deltas 3, 0 and 4 experts
    assert rec["trace"]["decode_least_bytes"] == \
        3000.0 + 1 + 0.0 + 3 + 4000.0 + 12
    assert seen == [{"experts_hit": 3.0},
                    {"experts_hit": 0.0, "new{kind=x}": 1.0},
                    {"experts_hit": 4.0, "new{kind=x}": 3.0}]


def test_profiler_reads_counters_at_the_slice_ends_only_unless_asked():
    reads = []

    def counters():
        reads.append(len(reads))
        return {"n": float(len(reads))}

    for every_tick, want in ((False, {2: 1.0, 5: 2.0}),
                             (True, {2: 1.0, 3: 2.0, 4: 3.0, 5: 4.0})):
        reads.clear()
        prof = cellrun._Profiler(0.0, 10.0, None, counters,
                                 every_tick=every_tick)
        prof.length = 1.0
        ticks = []
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(cellrun.jax.profiler, "start_trace", lambda d: None)
            mp.setattr(cellrun.jax.profiler, "stop_trace", lambda: None)
            mp.setattr(cellrun.time, "perf_counter", lambda: 4.0)
            for now in (1.0, 3.0, 4.0, 4.5, 4.8, 5.5, 6.0):
                prof.hook(now, ticks)
                ticks.append(now)
        assert (prof.k0, prof.k1) == (2, 5)
        assert {k: v["n"] for k, v in prof.at.items()} == want
