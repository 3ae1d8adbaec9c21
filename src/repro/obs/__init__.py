"""repro.obs — flight-recorder tracing, unified metrics, profiling hooks.

The observability subsystem that makes the fault-taxonomy recovery paths
*witnessable* instead of merely survivable:

``trace.py``
    Zero-dependency structured span tracer: nested spans with
    monotonic-clock timestamps (injectable for determinism), per-event
    attributes, and ``fault.<kind>`` / ``recover.<kind>`` annotations.
    An injected ``annotate`` factory copies every span's name onto the
    profiler's clock as well.
    :data:`NULL_TRACER` is the always-safe disabled default — one branch on
    the hot path, no allocation.

``recorder.py``
    Bounded flight-recorder ring buffer; dumps the last-N-seconds window as
    JSONL + Chrome ``trace_event`` JSON whenever a fault fires or a
    recovery path is taken (``dump_on_fault``), capped per run.

``metrics.py``
    Unified counters/gauges/histograms with labeled series, Prometheus-text
    and JSON exporters.  Absorbs ``serve/metrics.py`` and the training
    coordinator's inline counters behind one API.

``profile.py``
    Wraps jitted step functions: compile time, per-step wall time, optional
    ``cost_analysis`` FLOPs via ``repro.analysis.hlo`` — feeding
    ``benchmarks/roofline.py --profile``.

``validate.py``
    Dump schema validation + required-span assertions (the CI obs smoke).

The launchers build one :class:`ObsContext` via :func:`setup` from their
``--trace-dir`` / ``--trace-dump-on-fault`` flags and thread
``ctx.tracer`` / ``ctx.registry`` through the engine, coordinator, cluster,
checkpoint store and chaos engine.  With no trace dir everything collapses
to :data:`NULL_TRACER` and a detached registry: chaos-matrix replays are
byte-identical with tracing on or off, and the disabled recorder costs one
branch per call site.
"""
from __future__ import annotations

import dataclasses
import time

from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .profile import ProfiledFn, profile_jit, save_profiles
from .recorder import FlightRecorder, load_jsonl, to_chrome
from .trace import NULL_TRACER, Span, Tracer

__all__ = [
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_TRACER",
    "ObsContext",
    "ProfiledFn",
    "Span",
    "Tracer",
    "load_jsonl",
    "profile_jit",
    "save_profiles",
    "setup",
    "to_chrome",
]


@dataclasses.dataclass
class ObsContext:
    """One run's observability handles (tracer + recorder + registry)."""

    tracer: Tracer
    recorder: FlightRecorder | None
    registry: MetricsRegistry

    @property
    def enabled(self) -> bool:
        return self.tracer.enabled

    def finish(self, label: str = "run_end") -> str | None:
        """Final dump + metrics export into the trace dir (no-op when
        tracing is disabled).  Returns the JSONL dump path."""
        if self.recorder is None or self.recorder.out_dir is None:
            return None
        path = self.recorder.dump(label)
        self.registry.write(self.recorder.out_dir)
        return path


def setup(trace_dir: str | None = None, *, dump_on_fault: bool = False,
          capacity: int = 8192, window_s: float | None = None,
          max_dumps: int = 64, clock=time.monotonic,
          registry: MetricsRegistry | None = None) -> ObsContext:
    """Build an :class:`ObsContext`.  ``trace_dir=None`` disables tracing
    (NULL tracer, no recorder) but still returns a live registry.  With a
    trace dir every span is also a ``jax.profiler.TraceAnnotation``, so a
    JAX profile taken during the run shows the spans beside the device's
    operations (an annotation costs under a microsecond when no profile
    is running)."""
    registry = registry or MetricsRegistry()
    if trace_dir is None:
        return ObsContext(tracer=NULL_TRACER, recorder=None,
                          registry=registry)
    recorder = FlightRecorder(capacity, out_dir=trace_dir,
                              window_s=window_s,
                              dump_on_fault=dump_on_fault,
                              max_dumps=max_dumps, clock=clock)
    from jax.profiler import TraceAnnotation
    return ObsContext(tracer=Tracer(recorder, clock=clock,
                                    annotate=TraceAnnotation),
                      recorder=recorder, registry=registry)
