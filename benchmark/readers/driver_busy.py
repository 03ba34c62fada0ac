"""driver_busy.train: share of the ``driver`` lane's slice outside
``driver/host_wait``: what the driver thread needs for itself (fetch,
dispatch, bookkeeping, summaries); the rest it waits for a loss."""
from benchmark.readers import spans


def read(record, metric):
    return spans.share_outside(spans.lane("driver"), ("driver/host_wait",))
