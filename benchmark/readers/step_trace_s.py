"""step_trace_s: sum of trace_ms over CachedStep.timings of every step the
cell compiled or loaded."""


def read(record, metric):
    t = record.get("timings")
    if not t:
        return None
    return sum(float(x.get("trace_ms") or 0.0) for x in t) / 1e3
