"""engine_hbm_gb.serve: the gauges ``LM/weight_bytes`` + ``LM/pool_bytes``,
in GB: what the engine holds while it serves."""
from benchmark.readers import counters


def read(record, metric):
    weights = counters.value("LM/weight_bytes", "gauge")
    return (weights + counters.value("LM/pool_bytes", "gauge")) / 1e9 \
        if weights else None
