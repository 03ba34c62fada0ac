"""data_wait.train: the driver thread's wait for the next batch, as
StepAccount measures it (host clock around the fetch), share of the run."""


def read(record, metric):
    f = (record.get("train") or {}).get("data_wait_frac")
    return None if f is None else 100.0 * f
