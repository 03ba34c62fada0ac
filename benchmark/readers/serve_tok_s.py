"""serve_tok_s: completed requests' tokens received in the window / window."""


def read(record, metric):
    c = record.get("client")
    return c["serve_tok_s"] if c else None
