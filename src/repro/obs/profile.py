"""Profiling hooks for jitted step functions.

:func:`profile_jit` wraps a jit'd callable and records, into the unified
metrics registry and (optionally) the span tracer:

* **compile time** — the first call pays trace + XLA compile; its wall time
  lands in ``profile_compile_seconds{step=<name>}`` (the steady-state
  histogram starts at call 2);
* **per-step wall time** — every later call is timed end-to-end
  (``jax.block_until_ready`` on the outputs, so async dispatch cannot hide
  the work) into ``profile_step_seconds`` histogram series;
* **cost analysis** — :meth:`ProfiledFn.capture_cost` lowers + compiles the
  wrapped function for a concrete arg set and reads
  ``Compiled.cost_analysis()``, recording FLOPs / bytes-accessed gauges.

:func:`save_profiles` writes the collected profiles as JSON for
``benchmarks/roofline.py --profile``, which joins measured step times
against the analytic roofline terms (achieved vs. peak FLOP/s).

``block_until_ready`` makes the wrapper a synchronization point, so the
hooks are opt-in (the launchers enable them only under ``--trace-dir``);
results are bit-identical either way — only dispatch overlap changes.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax

from .metrics import MetricsRegistry
from .trace import NULL_TRACER

__all__ = ["ProfiledFn", "profile_jit", "save_profiles"]

STEP_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                2.5, 5.0, 15.0, 60.0)


@dataclasses.dataclass
class _Stats:
    compile_s: float | None = None
    calls: int = 0               # steady-state calls (compile call excluded)
    total_s: float = 0.0
    min_s: float = float("inf")
    max_s: float = 0.0
    flops: float | None = None
    bytes_accessed: float | None = None


class ProfiledFn:
    """A jit'd callable wrapped with wall-time + compile-time recording."""

    def __init__(self, fn, *, name: str, registry: MetricsRegistry | None,
                 tracer=None, clock=time.perf_counter):
        self.fn = fn
        self.name = name
        self.registry = registry or MetricsRegistry()
        self.tracer = tracer or NULL_TRACER
        self.clock = clock
        self.stats = _Stats()
        self._g_compile = self.registry.gauge(
            "profile_compile_seconds",
            "first-call (trace + XLA compile) wall time per step fn",
            ("step",))
        self._h_step = self.registry.histogram(
            "profile_step_seconds",
            "steady-state per-call wall time per step fn", ("step",),
            buckets=STEP_BUCKETS)
        self._g_flops = self.registry.gauge(
            "profile_step_flops",
            "XLA cost_analysis FLOPs per call of the step fn", ("step",))
        self._g_bytes = self.registry.gauge(
            "profile_step_bytes_accessed",
            "XLA cost_analysis bytes accessed per call", ("step",))

    def __call__(self, *args, **kwargs):
        t0 = self.clock()
        out = self.fn(*args, **kwargs)
        jax.block_until_ready(out)
        dt = self.clock() - t0
        st = self.stats
        if st.compile_s is None:
            st.compile_s = dt
            self._g_compile.set(dt, step=self.name)
            self.tracer.event("profile.compile", step=self.name, seconds=dt)
        else:
            st.calls += 1
            st.total_s += dt
            st.min_s = min(st.min_s, dt)
            st.max_s = max(st.max_s, dt)
            self._h_step.observe(dt, step=self.name)
        return out

    # -- optional XLA cost analysis -------------------------------------------
    def capture_cost(self, *args, **kwargs) -> dict:
        """Lower + compile for these concrete args and record FLOPs/bytes
        (uses the jit cache's lowering path; one extra compile at most)."""
        lowered = self.fn.lower(*args, **kwargs)
        cost = lowered.compile().cost_analysis()
        flops = float(cost.get("flops", 0.0))
        nbytes = float(cost.get("bytes accessed", 0.0))
        self.stats.flops = flops
        self.stats.bytes_accessed = nbytes
        self._g_flops.set(flops, step=self.name)
        self._g_bytes.set(nbytes, step=self.name)
        return cost

    def report(self) -> dict:
        st = self.stats
        mean = st.total_s / st.calls if st.calls else None
        return {
            "name": self.name,
            "compile_s": st.compile_s,
            "calls": st.calls,
            "total_s": st.total_s,
            "mean_s": mean,
            "min_s": None if st.calls == 0 else st.min_s,
            "max_s": None if st.calls == 0 else st.max_s,
            "flops": st.flops,
            "bytes_accessed": st.bytes_accessed,
            "achieved_flops_per_s": (st.flops / mean
                                     if st.flops and mean else None),
        }


def profile_jit(fn, *, name: str, registry: MetricsRegistry | None = None,
                tracer=None, clock=time.perf_counter) -> ProfiledFn:
    """Wrap a jit'd callable with compile/step wall-time recording."""
    return ProfiledFn(fn, name=name, registry=registry, tracer=tracer,
                      clock=clock)


def save_profiles(path: str, profiled: list[ProfiledFn]) -> str:
    """Write ``[ProfiledFn.report(), ...]`` as the ``profile.json``
    artifact ``benchmarks/roofline.py --profile`` consumes."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump([p.report() for p in profiled], f, indent=1,
                  sort_keys=True)
    return path
