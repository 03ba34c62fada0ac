"""device_idle.*: 1 - union of device-operation intervals over the traced
slice, worst chip."""


def read(record, metric):
    tr = record.get("trace")
    return 100.0 * tr["idle_share_worst"] if tr else None
