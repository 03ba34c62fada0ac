"""latent_fetch_ratio.serve: ``LM/kv_rows_fetched`` over
``LM/attn_selected_tokens`` (a program without either counter reads 0,
and the metric is left out)."""
from benchmark.readers import counters


def read(record, metric):
    fetched = counters.value("LM/kv_rows_fetched", "counter")
    used = counters.value("LM/attn_selected_tokens", "counter")
    return fetched / used if fetched and used else None
