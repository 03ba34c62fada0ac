"""experts_held_share.serve: ``LM/moe_assignments_held`` over
``LM/moe_assignments``, percent."""
from benchmark.readers import counters


def read(record, metric):
    return counters.share("LM/moe_assignments_held", "LM/moe_assignments")
