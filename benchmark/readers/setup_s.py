"""setup_s: first line of the command to the opening of the window."""


def read(record, metric):
    return record.get("setup_s")
