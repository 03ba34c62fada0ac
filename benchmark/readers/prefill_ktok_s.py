"""prefill_ktok_s.serve: ``LM/prompt_tokens`` over the sum of
``LM/prefill_ms`` (tokens a millisecond are thousands a second)."""
from benchmark.readers import counters


def read(record, metric):
    tokens = counters.value("LM/prompt_tokens", "counter")
    ms = counters.histogram_sum("LM/prefill_ms")
    return tokens / ms if tokens and ms else None
