"""itl_p95_ms: gaps between successive tokens at the client, 95th percentile."""


def read(record, metric):
    c = record.get("client")
    return c["itl_p95_ms"] if c else None
