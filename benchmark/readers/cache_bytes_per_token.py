"""cache_bytes_per_token.serve: the gauge ``LM/cache_bytes_per_token``."""
from benchmark.readers import counters


def read(record, metric):
    return counters.value("LM/cache_bytes_per_token", "gauge") or None
