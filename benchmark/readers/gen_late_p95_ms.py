"""gen_late_p95_ms: how long after its due time each request was handed to
submit(), 95th percentile over all requests offered."""


def read(record, metric):
    c = record.get("client")
    return c["gen_late_p95_ms"] if c else None
