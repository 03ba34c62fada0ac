"""decode_ema_ms.serve: stats()["decode_ema_ms"] at the end of the window;
the server's own host-clock moving average of one decode iteration."""


def read(record, metric):
    return (record.get("server") or {}).get("decode_ema_ms")
