"""collective_exposed.train: share of the traced slice in which a
collective runs on a chip and nothing else does, worst chip."""


def read(record, metric):
    tr = record.get("trace")
    if not tr or tr.get("chips", 0) < 2:
        return None
    return 100.0 * tr["collective_exposed_share_worst"]
