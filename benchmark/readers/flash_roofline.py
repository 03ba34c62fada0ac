"""flash_roofline.train: the least time the chip could take for the flash
attention calls in the traced slice (benchmark/ops.py, from B, heads, T,
head size, causal) over the summed device time of the Mosaic kernel
events.  Forward, dkv and dq kernels together: three events a layer a
step.  Returns nothing when the trace shows no such event."""
from benchmark import ops


def read(record, metric):
    tr, f = record.get("trace"), record.get("flash")
    if not tr or not f or not tr.get("kernel_events"):
        return None
    peak = ops.peaks(record["device_kind"])
    cost = ops.flash_attention_cost(f["batch"], f["heads"], f["seq_len"],
                                    f["head_dim"], causal=True)
    least = sum(ops.roofline_seconds(c["flops"], c["bytes"], peak)[0]
                for c in cost.values())
    calls = tr["kernel_events"] / tr["chips"] / 3.0   # fwd + dkv + dq
    return 100.0 * calls * least / tr["kernel_s"]
