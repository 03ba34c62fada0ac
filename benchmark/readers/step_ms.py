"""step_ms.train: median gap between completed steps in the window (the
time each loss reached the host; never the dispatch time)."""


def read(record, metric):
    return (record.get("train") or {}).get("step_ms_median")
