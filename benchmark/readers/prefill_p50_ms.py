"""prefill_p50_ms.serve: median of ``LM/prefill_ms`` over every request
the scheduler admitted in the run (lead-in and window: the same replayed
trace): prefill dispatch to its log-probs on the host."""
from benchmark.readers import spans


def read(record, metric):
    return spans.histogram_percentile("LM/prefill_ms", 50)
