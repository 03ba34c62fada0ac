"""What the span and counter readers share.  They read the program's own
in-process state (``bigdl_tpu.telemetry``), which outlives the profiler's
files: the spans its threads recorded while the traced run's profiler
session armed them, one lane (thread) at a time, and the registry's
histograms.  A program that recorded nothing (its session never
overlapped the lane, or it is from before PR 24 and has no such span or
histogram) gives an empty lane or ``None``, and the reader leaves its
metric out of the line."""

from typing import List, Optional, Tuple

Span = Tuple[int, int, str]         # start, end (ns, the tracer's clock), name


def lane(thread: str) -> List[Span]:
    """The finished spans of the lane named ``thread``, by start."""
    from bigdl_tpu import telemetry
    return sorted((e["t0_ns"], e["t1_ns"], e["name"])
                  for e in telemetry.events()
                  if e["thread"] == thread and e["t1_ns"] is not None)


def slice_ns(spans: List[Span]) -> int:
    """First span's start to last span's end: the part of the run the
    session armed on this lane."""
    return max(s[1] for s in spans) - min(s[0] for s in spans)


def total_ns(spans: List[Span], names) -> int:
    return sum(t1 - t0 for t0, t1, name in spans if name in names)


def share_outside(spans: List[Span], names) -> Optional[float]:
    """Percent of the lane's slice not inside a span named in ``names``
    (which must not nest in each other)."""
    if not spans or slice_ns(spans) <= 0:
        return None
    return 100.0 * (1.0 - total_ns(spans, names) / slice_ns(spans))


def mean_ms(spans: List[Span], name: str) -> Optional[float]:
    durations = [t1 - t0 for t0, t1, n in spans if n == name]
    if not durations:
        return None
    return sum(durations) / len(durations) / 1e6


def histogram_percentile(name: str, q: float) -> Optional[float]:
    """Percentile ``q`` of the registry's histogram ``name`` over its
    window (512 observations: a whole run's requests), or ``None`` where
    it has none.  A program that keeps another kind of metric under the
    name (``LM/prefill_ms`` was a gauge before PR 24) has no histogram."""
    from bigdl_tpu import telemetry
    try:
        h = telemetry.histogram(name)
    except TypeError:
        return None
    return h.percentile(q) if h.count else None
