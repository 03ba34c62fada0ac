"""train_mfu: steps a second x operations a step over chips x bf16 peak."""
from benchmark import ops


def read(record, metric):
    t = record.get("train")
    if not t:
        return None
    peak = ops.peaks(record["device_kind"])["bf16_flops_per_s"]
    return 100.0 * t["steps_per_s"] * t["flops_per_step"] / (
        t["chips"] * peak)
