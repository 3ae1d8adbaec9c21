"""Where an engine tick's time goes: run a cell once, traced over the same
slice as a ``--trace 1`` run, with the program's own spans (``serve.*``)
written to the profiler beside the harness's annotations, and print the
device's idle time by the innermost span open during it.

    python3 bench/tools/tick_spans.py \
        --workload deepseek-coder-33b.chat-saturated --seed 5 --seconds 51

The benchmark's adapter gives the engine a tracer that writes to the
harness's recorder only; this tool gives it one that also opens a
``jax.profiler.TraceAnnotation`` per span.  Prints one JSON line:

* ``idle_by_span``: device-idle seconds of the slice by innermost open
  annotation (harness or program; ``none`` where none is open), and
  ``idle_gaps``, the longest gaps so tagged;
* ``tick_host_ms``: mean over the slice's ``serve.tick`` spans of their
  length less the time their ``*.wait`` children held the host on the
  device; ``tick_cover_min``: the least share of a tick its phases cover;
* ``snapshot_ms``: median ``serve.snapshot.take`` in the slice;
  ``snapshot_idle_share``: the share (%) of the idle time under
  ``serve.snapshot.*``;
* ``snapshot_mb``: the program's ``snapshot_bytes`` over its
  ``snapshots``, over the window;
* ``queue_wait_s``: median ``waited_s`` of the ``serve.start`` events of
  first admissions in the second half of the window;
* ``tick_ms_in`` / ``tick_ms_out``: mean ``serve.tick`` length on the
  recorder's clock for the ticks inside the profiled slice and outside it.
"""
import argparse
import bisect
import collections
import glob
import json
import os
import shutil
import statistics
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import adapter, cellrun, device, spec, window  # noqa: E402
from harness import trace as trace_mod  # noqa: E402
from harness.stats import gaps_between  # noqa: E402

PROGRAM_PREFIX = "serve."
TICK_SPAN = "serve.tick"
TICK_PHASES = ("serve.tick.faults", "serve.tick.shed", "serve.tick.admit",
               "serve.decode", "serve.tick.snapshots")
WAIT_SUFFIX = ".wait"     # a span in which the host waits for the device
SNAPSHOT_PREFIX = "serve.snapshot."


def program_events(log_dir: str) -> list:
    """``[name, start_ns, dur_ns]`` of the program's spans on the host
    planes of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [[e.name, e.start_ns, e.duration_ns]
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM_PREFIX)]


def innermost(annotations, lo: float, hi: float) -> list:
    """``[(start, end, name), ...]``: ``[lo, hi]`` cut where the innermost
    open annotation changes (``"none"`` where none is open), in order.
    Annotations of one thread nest; one that outlasts the annotation it
    opened in is cut at that one's end."""
    segs = []
    stack: list[tuple[float, str]] = []      # (end, name), innermost last
    cur = lo

    def emit(end, name):
        nonlocal cur
        end = min(max(end, lo), hi)
        if end > cur:
            segs.append((cur, end, name))
            cur = end

    for name, s, d in sorted(annotations, key=lambda a: (a[1], -a[2])):
        e = s + d
        while stack and stack[-1][0] <= s:
            emit(*stack.pop())
        emit(s, stack[-1][1] if stack else "none")
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        emit(*stack.pop())
    emit(hi, "none")
    return segs


def idle_by_span(gaps, segs) -> dict:
    """Seconds of the gaps (ns, sorted) under each innermost annotation
    (``segs`` from :func:`innermost`), in one sweep over both."""
    out: dict[str, float] = collections.defaultdict(float)
    j = 0
    for s, e in gaps:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            a, b, name = segs[k]
            out[name] += (min(b, e) - max(a, s)) * 1e-9
            k += 1
    return dict(out)


def _ticks(inside) -> list:
    """``[length, waited, covered]`` (ns) of each ``serve.tick`` among the
    sorted ``(start, end, name)`` spans: the time its ``*.wait`` spans held
    the host on the device, and the time its phases cover."""
    def within(s, e, names):
        return sum(min(b, e) - a for a, b, n in inside
                   if n in names and s <= a < e)

    waits = {n for _, _, n in inside if n.endswith(WAIT_SUFFIX)}
    return [[e - s, within(s, e, waits), within(s, e, TICK_PHASES)]
            for s, e, n in inside if n == TICK_SPAN]


def where(events: dict, top: int = 10) -> dict:
    """The idle time and the program's spans of a trace: ``events`` as
    ``harness.trace.extract`` gives them, with the program's spans under
    ``"program"``.  The slice and the busy time are the harness's."""
    host, program = events["host"], events["program"]
    lo = min(s for _, s, _ in host)
    hi = max(s + d for _, s, d in host)
    ops = events["ops"] or events["modules"]
    spans = [(max(s, lo), min(s + d, hi)) for _, s, d in ops
             if s + d > lo and s < hi]
    gaps = gaps_between(spans, lo, hi)
    segs = innermost(host + program, lo, hi)
    starts = [a for a, _, _ in segs]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    by = idle_by_span(gaps, segs)
    idle = sum(by.values())
    inside = sorted((s, s + d, n) for n, s, d in program
                    if s >= lo and s + d <= hi)
    takes = [(e - s) * 1e-9 for s, e, n in inside
             if n == "serve.snapshot.take"]
    ticks = _ticks(inside)
    return {
        "slice_s": (hi - lo) * 1e-9, "idle_s": idle, "idle_by_span": by,
        "idle_gaps": [[segs[bisect.bisect_right(starts, (s + e) / 2) - 1][2],
                       (e - s) * 1e-9] for s, e in longest],
        "snapshot_idle_share": 100.0 * sum(
            v for n, v in by.items() if n.startswith(SNAPSHOT_PREFIX))
        / idle if idle else None,
        "snapshot_ms": 1e3 * statistics.median(takes) if takes else None,
        "tick_host_ms": 1e-6 * sum(t - w for t, w, _ in ticks) / len(ticks)
        if ticks else None,
        "tick_cover_min": min(c / t for t, _, c in ticks) if ticks else None,
        "ticks": len(ticks)}


def run(workload: str, *, seed: int, seconds: float, bench=None,
        bench_dir=spec.BENCH_DIR, require_accelerator=True) -> dict:
    """Run ``workload`` once, traced, and return the line.  ``bench``,
    ``bench_dir`` and ``require_accelerator=False`` are for the tests,
    which run it on the CPU at a tiny size."""
    import jax

    from repro.obs.trace import Tracer
    cell = spec.load_cell(workload, bench=bench, bench_dir=bench_dir)
    if require_accelerator:
        device.require_tpu(cell.chips)
    adapter.compile_cache()
    system, specs, _ = cellrun.build(cell, seed=seed, seconds=seconds)
    system.engine.tracer = Tracer(system._rec,
                                  annotate=jax.profiler.TraceAnnotation)
    kept = []                   # (tick index, record)
    take = system.take_records

    def tee():
        recs = take()
        kept.extend((tee.k, r) for r in recs
                    if r["name"] in ("serve.start", TICK_SPAN))
        tee.k += 1
        return recs
    tee.k = 0
    system.take_records = tee

    log_dir = tempfile.mkdtemp(prefix="tick-spans-")
    prof = None

    def hook(now, ticks):
        nonlocal prof
        if prof is None:
            prof = cellrun._Profiler(now, seconds, log_dir,
                                     system.counters)
        prof.hook(now, ticks)

    try:
        res = window.run(system, specs, seconds=seconds, hook=hook)
        prof.close(res.ticks)
        events = trace_mod.extract(log_dir)
        events["program"] = program_events(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    m = system.engine.metrics
    out = where(events)
    out["snapshot_mb"] = (m.snapshot_bytes / m.snapshots / 1e6
                          if m.snapshots else None)
    half = res.t0 + res.seconds / 2
    waits = [r["attrs"]["waited_s"] for k, r in kept
             if r["name"] == "serve.start" and r["attrs"]["first"]
             and res.ticks[k].t0 >= half]
    out["queue_wait_s"] = statistics.median(waits) if waits else None
    tick_s = {True: [], False: []}
    for k, r in kept:
        if r["name"] == TICK_SPAN:
            tick_s[prof.k0 <= k < prof.k1].append(r["t1"] - r["t0"])
    for key, inside in (("tick_ms_in", True), ("tick_ms_out", False)):
        v = tick_s[inside]
        out[key] = 1e3 * sum(v) / len(v) if v else None
    out["device_ops"] = trace_mod.reduce(events)["breakdown"]["device_ops"]
    system.free()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args()
    try:
        out = run(a.workload, seed=a.seed, seconds=a.seconds)
    except device.NoAccelerator as e:
        print(f"tick_spans: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
