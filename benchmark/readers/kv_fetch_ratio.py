"""kv_fetch_ratio.serve: ``LM/kv_rows_fetched`` over
``LM/attn_context_tokens`` (a program from before the counter reads 0
fetched, and the metric is left out)."""
from benchmark.readers import counters


def read(record, metric):
    fetched = counters.value("LM/kv_rows_fetched", "counter")
    context = counters.value("LM/attn_context_tokens", "counter")
    return fetched / context if fetched and context else None
