"""queue_wait_p90_ms.serve: 90th percentile of ``LM/queue_wait_ms`` over
every request the scheduler admitted in the run: submit to the
scheduler's pop."""
from benchmark.readers import spans


def read(record, metric):
    return spans.histogram_percentile("LM/queue_wait_ms", 90)
