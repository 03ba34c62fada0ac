"""decode_pull_bytes.serve: ``LM/decode_pulled_bytes`` over
``LM/decode_steps`` (a program from before the counter reads 0 pulled,
and the metric is left out)."""
from benchmark.readers import counters


def read(record, metric):
    pulled = counters.value("LM/decode_pulled_bytes", "counter")
    steps = counters.value("LM/decode_steps", "counter")
    return pulled / steps if pulled and steps else None
