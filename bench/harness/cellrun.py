"""One run of one cell: set-up, the measured window, the check, the line.

Set-up: the cell's files, the program's compile cache, the seeded
requests, the weights made on the device from the seed, the replica
policy fitted on the run's own requests, and every program the window can
run compiled or loaded from the cache.  ``setup_s`` runs from process
start to the first due request.

The window runs open-loop for ``--seconds``.  With ``--trace 1`` the
profiler records a slice from 40% of the window for a quarter of it (at
most ten seconds), starting and stopping at tick boundaries, and the
line carries the per-layer metrics; with ``--trace 0`` it carries the
end-to-end ones.

After the window: the device's memory peak is read, the program's state
is freed, and a seeded sample of the finished requests is checked against
the configuration's float32 reference.  The numbers compared are printed
beside their limits as the last lines of standard error and under
``checks``, the last key of the result line on standard output.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time

import jax

from . import adapter, check, counts, device, spec, traffic, window
from . import trace as trace_mod
from .stats import percentile

TRACE_AT = 0.4        # share of the window before the traced slice
TRACE_SHARE = 0.25    # share of the window traced
TRACE_MAX_S = 10.0


class _Compiles:
    """Counts JAX compilations (tracing, lowering or backend compiles)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.n += 1


class _Profiler:
    """Starts and stops the profiler at tick boundaries, and reads the
    program's counters (``counters()``) just before it starts and just
    after it stops; with ``every_tick``, also at each tick boundary in
    between, for counts that read a tick's counters."""

    def __init__(self, t0: float, seconds: float, log_dir: str, counters,
                 every_tick: bool = False):
        self.start_at = t0 + TRACE_AT * seconds
        self.length = min(TRACE_SHARE * seconds, TRACE_MAX_S)
        self.log_dir = log_dir
        self.counters = counters
        self.every_tick = every_tick
        self.at: dict[int, dict] = {}   # ticks done -> counters then
        self.k0 = self.k1 = None
        self.started = None

    def hook(self, now: float, ticks: list) -> None:
        if self.k0 is None and now >= self.start_at:
            self.at[len(ticks)] = self.counters()
            jax.profiler.start_trace(self.log_dir)
            self.k0, self.started = len(ticks), time.perf_counter()
        elif (self.k0 is not None and self.k1 is None
              and now >= self.started + self.length):
            self._stop(ticks)
        elif self.every_tick and self.k0 is not None and self.k1 is None:
            self.at[len(ticks)] = self.counters()

    def _stop(self, ticks: list) -> None:
        jax.profiler.stop_trace()
        self.k1 = len(ticks)
        self.at[self.k1] = self.counters()

    def close(self, ticks: list) -> None:
        if self.k0 is not None and self.k1 is None:
            self._stop(ticks)

    def tick_counters(self) -> list[dict]:
        """The counters' change over each traced tick."""
        return [_delta(self.at[j], self.at[j + 1])
                for j in range(self.k0, self.k1)]


def _delta(a: dict, b: dict) -> dict:
    """``b - a`` for every counter of either (a missing series is 0)."""
    return {k: b.get(k, 0.0) - a.get(k, 0.0) for k in {**a, **b}}


def _say(err, line: str) -> None:
    print(line, file=err, flush=True)


def build(cell: spec.Cell, *, seed: int, seconds: float,
          rate_rps: float | None = None, break_path=None):
    """The served path for ``cell`` from ``seed``, warmed: returns the
    system, the window's requests and the reference's sizes.  Used by the
    run and by the tools (``rate_rps`` is the sweep's)."""
    model = cell.config["model"]
    m = {**model, **cell.config["reference"]}
    specs = traffic.generate(
        cell.traffic, rate_rps=rate_rps or cell.cell["rate_rps"],
        seconds=seconds, seed=seed, vocab_size=model["vocab_size"])
    system = adapter.System(
        model, cell.config["deployment"], cell.traffic,
        init_fn=lambda key: cell.reference.init(key, m),
        key=check.weight_key(seed), chips=cell.chips, requests=specs)
    if break_path is not None:
        break_path(system)
    system.warm([s.prompt.shape[0] for s in specs])
    return system, specs, m


def run_cell(workload: str, *, seed: int, seconds: float, trace: bool,
             t_proc: float, bench: dict | None = None,
             bench_dir=spec.BENCH_DIR, require_accelerator: bool = True,
             break_path=None, keep_trace=None, control=False,
             out=sys.stdout, err=sys.stderr) -> dict:
    """Run ``workload`` once and print its result line; returns it.

    ``require_accelerator=False`` and ``break_path`` are for the tests,
    which drive a run on the CPU at a tiny size with the timed path broken
    underneath (``break_path(system)``) and look for ``correct`` false.
    ``keep_trace`` (a path) keeps the extracted trace events as JSON for
    ``tools/trace_dump.py``.  ``control`` compares the float8 control's
    tokens in place of the served ones, which must read ``correct``
    false."""
    cell = spec.load_cell(workload, bench=bench, bench_dir=bench_dir)
    if require_accelerator:
        device.require_tpu(cell.chips)
    adapter.compile_cache()
    compiles = _Compiles()
    t_start = time.time() - t_proc
    system, specs, m = build(cell, seed=seed, seconds=seconds,
                             break_path=break_path)
    setup_s = time.time() - t_proc
    _say(err, f"set-up {setup_s:.3f} s ({t_start:.3f} s to the harness): "
              f"{len(specs)} requests due in {seconds} s at "
              f"{cell.cell['rate_rps']} req/s, policy {system.policy_name}, "
              f"{compiles.n} compile events")

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    prof = None
    tally = counts.Counts(cell.reference)
    before = compiles.n
    try:
        def hook(now, ticks):
            nonlocal prof
            if prof is None:
                prof = _Profiler(now, seconds, log_dir, system.counters,
                                 every_tick=tally.per_tick_counters)
            prof.hook(now, ticks)

        res = window.run(system, specs, seconds=seconds,
                         hook=hook if trace else None)
        if prof is not None:
            prof.close(res.ticks)
        in_window = compiles.n - before
        dev = device.record(cell.chips)
        outputs = system.outputs()
        system.free()
        reduced = None
        if trace:
            events = trace_mod.extract(log_dir)
            if keep_trace:
                with open(keep_trace, "w") as f:
                    json.dump(events, f)
            reduced = trace_mod.reduce(events)
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    late = sorted(res.lateness) or [0.0]
    _say(err, f"window {res.seconds:.3f} s: {len(res.ticks)} ticks, "
              f"{res.delivered} tokens delivered, "
              f"{sum(c.done for c in res.clients.values())}/"
              f"{len(res.clients)} requests finished, backlog "
              f"{res.backlog(res.t0 + res.seconds / 2)} at mid-window and "
              f"{res.backlog(res.t1)} at the close, counters "
              f"{res.counters_end}")
    _say(err, f"generator lateness (s): mean {sum(late) / len(late):.4f} "
              f"p99 {percentile(late, 99):.4f} max {late[-1]:.4f}; "
              f"compile events inside the window: {in_window}")

    rec = _record(cell, tally, m, res, setup_s, dev, reduced, prof)
    if reduced is not None:
        _say(err, f"trace: slice {reduced['slice_s']:.4f} s, busy "
                  f"{reduced['busy_s']:.4f} s, programs "
                  f"{json.dumps(reduced['programs'])}, traced ticks "
                  f"{rec['trace']['decode_ticks']}")

    checks, ctrl = check_outputs(cell, m, seed, outputs, res, specs,
                                 control=control)
    if control:
        _say(err, "control: the float8 reference's tokens are compared in "
                  f"place of the served ones (served: widest gap "
                  f"{checks['max_logit_gap']['value']})")
        checks = ctrl
    correct = all(c["value"] <= c["limit"] if c["kind"] == "max"
                  else c["value"] >= c["limit"] for c in checks.values())

    metrics = {}
    for entry in (cell.per_layer if trace else cell.end_to_end):
        value = cell.reader(entry["name"]).read(rec)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    if trace:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["slice_s"]
    c_end = res.counters_end
    result = {"correct": bool(correct), "attempted": len(res.clients),
              "failed": int(c_end["shed"] + c_end["rejected"]),
              "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    for k, v in checks.items():
        _say(err, f"check {k}: {v['value']} (limit: {v['kind']} "
                  f"{v['limit']})")
    print(json.dumps(result), file=out, flush=True)
    return result


def _record(cell, tally, m, res, setup_s, dev, reduced, prof) -> dict:
    """What the metric readers read.  ``tally`` is the configuration's
    ``counts.Counts``; with a trace, ``counters_start`` and
    ``counters_stop`` are the program's counters at the slice's two ends
    (``adapter.System.counters``)."""
    flops = counts.window_flops(tally, m, res.ticks)
    ttft = [(c.first if c.first is not None else res.t1) - c.due
            for c in res.clients.values()]
    itl = [g for c in res.clients.values() for g in c.gaps]
    rec = {"seconds": res.seconds, "chips": cell.chips, "setup_s": setup_s,
           "delivered_tokens": res.delivered, "ttft_s": ttft, "itl_s": itl,
           "flops": flops, "peaks": spec.peaks_for(dev["kind"],
                                                   cell.bench_dir),
           "counters_mid": res.counters_mid,
           "counters_end": res.counters_end,
           "programs": {"decode": adapter.DECODE_PROGRAM,
                        "prefill": adapter.PREFILL_PROGRAM}}
    if reduced is not None:
        traced = res.ticks[prof.k0:prof.k1]
        per_tick = prof.tick_counters() if tally.per_tick_counters else None
        rec["trace"] = dict(
            reduced,
            decode_ticks=sum(bool(t.decoded) for t in traced),
            decode_least_bytes=counts.slice_least_bytes(tally, m, traced,
                                                        per_tick),
            prefill_padded_tokens=sum(s for t in traced
                                      for _, s in t.prefills),
            counters_start=prof.at[prof.k0],
            counters_stop=prof.at[prof.k1])
    return rec


def _limits(worst: float, n: int, lim: dict) -> dict:
    return {"max_logit_gap": {"value": worst, "limit": lim["max_logit_gap"],
                              "kind": "max"},
            "tokens_compared": {"value": n, "limit": lim["min_tokens"],
                                "kind": "min"}}


def check_outputs(cell, m, seed, outputs, res, specs, *, control=False):
    """The numbers compared, each beside its limit, for a seeded sample
    of the finished requests; with ``control``, also the same numbers for
    the float8 control put in the program's place (at each position of the
    same prompts and served tokens, the token the control puts first).
    Returns ``(program, control or None)``."""
    lim = cell.cell["check"]
    chosen = check.sample(outputs, seed=seed, restored=res.restored,
                          replicated=res.replicated,
                          tokens=lim["sample_tokens"],
                          max_requests=lim["max_requests"])
    ref = check.Reference(cell.reference, m, seed,
                          cell.config["deployment"]["cache_len"])
    worst, worst8, n = 0.0, 0.0, 0
    for rid in chosen:
        served = outputs[rid]
        l32 = ref.logits(specs[rid].prompt, served)
        worst = max(worst, float(check.gaps(l32, served).max()))
        if control:
            l8 = ref.logits(specs[rid].prompt, served, dtype="float8_e4m3fn")
            worst8 = max(worst8, float(check.gaps(l32, l8.argmax(-1)).max()))
        n += len(served)
    ref.free()
    return (_limits(worst, n, lim),
            _limits(worst8, n, lim) if control else None)
