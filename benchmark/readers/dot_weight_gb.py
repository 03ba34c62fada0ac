"""dot_weight_gb.serve: the gauge ``LM/dot_weight_bytes``, in GB (a
program without the gauge reads 0, and the metric is left out)."""
from benchmark.readers import counters


def read(record, metric):
    held = counters.value("LM/dot_weight_bytes", "gauge")
    return held / 1e9 if held else None
