"""decode_ahead_share.serve: ``LM/decode_steps_ahead`` over
``LM/decode_steps``, in percent (a program from before the counter reads 0
steps ahead, and the metric is left out)."""
from benchmark.readers import counters


def read(record, metric):
    if not counters.value("LM/decode_steps_ahead", "counter"):
        return None
    return counters.share("LM/decode_steps_ahead", "LM/decode_steps")
