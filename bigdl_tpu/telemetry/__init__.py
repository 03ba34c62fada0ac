"""Runtime telemetry: span tracing, step-time decomposition, metrics.

The reference's observability was a throughput log line per iteration
(``optim/DistriOptimizer.scala:293-297``); the rebuild's driver-centric
loop needs to answer *where the step time goes* without printf
archaeology.  Three pillars, one package:

1. **Span tracer** (:mod:`~bigdl_tpu.telemetry.tracer`) —
   ``with telemetry.span("optim/device_step"): ...`` writes to per-thread
   ring buffers; :func:`export_chrome_trace` merges the driver hot loop,
   every ``StreamingIngest`` stage thread, the ``BatchPrefetcher``
   fetch/transfer threads, the async checkpoint writer, and the
   compile-warmup phase (``driver/compile_warmup`` wrapping one
   ``compile/<step>`` span per trace/cache-load/compile, from
   ``utils/compile_cache``) into one Perfetto-loadable timeline.  Armed
   explicitly (``bigdl.telemetry.trace``) or by a running ``jax.profiler``
   session, under which every span has a twin on the profiler's host
   plane; free otherwise; allocation-light and device-value-free when
   armed (the strict host-sync guard stays green over traced runs).
2. **Step-time decomposition** (:mod:`~bigdl_tpu.telemetry.step_stats`)
   — every optimizer step is accounted into data-wait / compute /
   host-pull / bookkeeping plus an explicit signed ``unaccounted``
   residual, surfaced as ``Telemetry/*`` TrainSummary scalars with
   rolling p50/p95/p99 latency; a slow-step detector (step > k·EMA) can
   trigger an on-demand ``jax.profiler`` capture and a timeline dump.
3. **Metrics registry** (:mod:`~bigdl_tpu.telemetry.metrics`) —
   counters/gauges/histograms with labeled names, ONE summary flush path
   (the driver's single emission loop), a per-run ``telemetry.json``
   snapshot, and a Prometheus text dump.  The pre-existing ``Ingest/*``
   and ``Analysis/*`` scalars route through it with unchanged tags.

Two forensic layers ride the pillars: per-request distributed tracing
(:mod:`~bigdl_tpu.telemetry.request_trace` — a trace id per serving/LM/
fleet submission, a causally-ordered span chain ending in the terminal
verdict, histogram exemplars for tail-latency lookup) and the incident
flight recorder (:mod:`~bigdl_tpu.telemetry.incident` — a bounded
structured-event ring plus one self-contained bundle per terminal
fault).

Configuration (``bigdl.telemetry.*`` / ``bigdl.trace.*`` /
``bigdl.incident.*`` in ``utils/config.py``); the knob table lives in
``docs/programming-guide/optimization.md``.
"""

from __future__ import annotations

from bigdl_tpu.telemetry.tracer import (add_span, add_span_s, arm, clock_ns,
                                        disarm, events, export_chrome_trace,
                                        instant, maybe_arm_from_config,
                                        name_thread, recording, self_times,
                                        span, tracing_enabled)
from bigdl_tpu.telemetry.tracer import reset as reset_tracer
from bigdl_tpu.telemetry.metrics import (Counter, Gauge, Histogram,
                                         MetricsRegistry, REGISTRY)
from bigdl_tpu.telemetry.step_stats import (PARTS, SlowStepDetector,
                                            StepAccount, WindowedPercentiles)
from bigdl_tpu.telemetry import incident, request_trace


def counter(name, labels=None, summary=False, help=""):
    """Shorthand for ``REGISTRY.counter(...)``."""
    return REGISTRY.counter(name, labels=labels, summary=summary, help=help)


def gauge(name, labels=None, summary=False, help=""):
    """Shorthand for ``REGISTRY.gauge(...)``."""
    return REGISTRY.gauge(name, labels=labels, summary=summary, help=help)


def histogram(name, labels=None, summary=False, help="", window=512):
    """Shorthand for ``REGISTRY.histogram(...)``."""
    return REGISTRY.histogram(name, labels=labels, summary=summary,
                              help=help, window=window)


def observe_interval(name, labels, span_name, t0_ns, t1_ns, help=""):
    """One interval timed by the caller's two :func:`clock_ns` reads,
    recorded twice: its milliseconds observed into the histogram
    ``name{labels}`` whether or not anything is armed (set-up runs before
    any profiler starts), and the span ``span_name`` added to this
    thread's lane while spans record.  Returns the milliseconds."""
    ms = (t1_ns - t0_ns) / 1e6
    REGISTRY.histogram(name, labels=labels, help=help).observe(ms)
    add_span(span_name, t0_ns, t1_ns)
    return ms


def setup_done(entry, span_name, t0_ns):
    """A set-up entry point's own time, from ``t0_ns`` to now:
    ``Setup/program_ms{entry=...}`` and the span ``span_name``."""
    return observe_interval(
        "Setup/program_ms", {"entry": entry}, span_name, t0_ns, clock_ns(),
        help="milliseconds of a set-up entry point: engine construction, "
             "engine warm-up, optimize() to step 1's dispatch")


def summary_scalars():
    """The one flush path: every chartable ``(tag, value)`` pair."""
    return REGISTRY.summary_scalars()


__all__ = [
    # tracer
    "span", "instant", "add_span", "add_span_s", "clock_ns", "arm",
    "disarm", "tracing_enabled", "maybe_arm_from_config", "name_thread",
    "events", "export_chrome_trace", "reset_tracer", "recording",
    "self_times",
    # metrics
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "counter", "gauge", "histogram", "observe_interval", "setup_done",
    "summary_scalars",
    # step stats
    "PARTS", "StepAccount", "WindowedPercentiles", "SlowStepDetector",
    # per-request tracing + incident flight recorder
    "request_trace", "incident",
]
