"""step_compile_s: sum of compile_ms and load_ms over CachedStep.timings."""


def read(record, metric):
    t = record.get("timings")
    if not t:
        return None
    return sum(float(x.get("compile_ms") or 0.0)
               + float(x.get("load_ms") or 0.0) for x in t) / 1e3
