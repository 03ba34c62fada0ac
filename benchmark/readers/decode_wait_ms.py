"""decode_wait_ms.serve: mean ``lm/decode_wait`` of the traced slice: the
decode step's device time still to run when the host asks for its
log-probs, plus their transfer."""
from benchmark.readers import spans


def read(record, metric):
    return spans.mean_ms(spans.lane("lm-scheduler"), "lm/decode_wait")
