"""From a profiler trace to the numbers the per-layer readers take.

Two steps, kept apart so the second can be checked on a small recorded
trace (``tests/data/trace-olmo-1b.json.gz``, cut from a chip run of
``olmo-1b.chat``):

* ``extract(log_dir)`` reads the ``.xplane.pb`` that ``jax.profiler``
  wrote and keeps three lists of ``[name, start_ns, dur_ns]``: the
  device's XLA modules (one event per program execution), its XLA ops (the
  operations inside them, named by the HLO instruction's result name
  only), and the harness's own host annotations
  (``bench.*``), all on the trace's one clock.
* ``reduce(events)`` gives the traced slice (from the first harness
  annotation to the end of the last), the device's busy time in it (the
  union of the op intervals), the executions and device seconds of each
  program, the longest idle gaps with the annotation the host was inside
  during each, the programs that took the most device time, and the
  device seconds of each XLA op by name (``op_seconds``), so that a
  kernel's reader finds its own time inside a program.  Ops nest (a
  ``while`` holds the ops of its body), so these do not add up to the
  busy time.
"""
from __future__ import annotations

import collections
import glob
import os
import re

from .stats import gaps_between, union_length

HOST_PREFIX = "bench."


def program_name(module: str) -> str:
    """``jit_serve_step_masked(1234)`` -> ``serve_step_masked``."""
    name = re.sub(r"\(\d+\)$", "", module.strip())
    return name[4:] if name.startswith("jit_") else name


def extract(log_dir: str) -> dict:
    """Events of the first device and of the host annotations, from the
    newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {"modules": [], "ops": [], "host": []}
    for plane in data.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    out[key] += [[e.name.split(" = ")[0], e.start_ns,
                                  e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name.startswith(HOST_PREFIX)]
    return out


def _host_at(host, t: float) -> str:
    """The innermost harness annotation open at ``t`` (ns)."""
    best = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "none"


def reduce(events: dict, top: int = 10) -> dict:
    host = events["host"]
    if not host:
        raise ValueError("trace holds no harness annotations")
    lo = min(s for _, s, _ in host)
    hi = max(s + d for _, s, d in host)
    ops = events["ops"] or events["modules"]
    spans = [(max(s, lo), min(s + d, hi)) for _, s, d in ops
             if s + d > lo and s < hi]
    busy_ns = union_length(spans)
    programs: dict[str, dict] = collections.defaultdict(
        lambda: {"count": 0, "device_s": 0.0})
    for name, s, d in events["modules"]:
        if s >= lo and s + d <= hi:
            p = programs[program_name(name)]
            p["count"] += 1
            p["device_s"] += d * 1e-9
    op_seconds: dict[str, float] = collections.defaultdict(float)
    for name, s, d in events["ops"]:
        if s >= lo and s + d <= hi:
            op_seconds[name] += d * 1e-9
    gaps = sorted(gaps_between(spans, lo, hi), key=lambda g: g[0] - g[1])
    idle = [[_host_at(host, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:top]]
    device_ops = sorted(([n, p["device_s"]] for n, p in programs.items()),
                        key=lambda x: -x[1])[:top]
    return {"slice_s": (hi - lo) * 1e-9, "busy_s": busy_ns * 1e-9,
            "programs": dict(programs), "op_seconds": dict(op_seconds),
            "breakdown": {"device_ops": device_ops, "idle_gaps": idle}}
