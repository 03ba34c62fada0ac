"""emit_ms.serve: mean ``lm/emit`` of the traced slice: arg-max, emit,
finish and free for every occupied slot, in Python."""
from benchmark.readers import spans


def read(record, metric):
    return spans.mean_ms(spans.lane("lm-scheduler"), "lm/emit")
