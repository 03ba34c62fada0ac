"""slot_occupancy.serve: growth of tokens_out over growth of decode_steps x
max_batch across the window, from LMServingEngine.stats()."""


def read(record, metric):
    s = record.get("server")
    if not s or not s["decode_steps_in_window"]:
        return None
    return 100.0 * s["decode_tokens_in_window"] / (
        s["decode_steps_in_window"] * s["max_batch"])
