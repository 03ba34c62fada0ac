"""sched_host_share.serve: share of the ``lm-scheduler`` lane's slice
outside ``lm/decode_wait``, ``lm/prefill_wait`` and ``lm/idle_wait``: the
scheduler thread's own host work, during which the device has nothing
queued."""
from benchmark.readers import spans

WAITS = ("lm/decode_wait", "lm/prefill_wait", "lm/idle_wait")


def read(record, metric):
    return spans.share_outside(spans.lane("lm-scheduler"), WAITS)
