"""A train cell is listed under exactly the per-layer metrics its traced
line can hold, in either family; and the job's optimizer has one spelling.

The role runs here, in this process, at toy widths, with the profiler on
as in a ``--trace 1`` run (so the driver's spans are recorded).  A CPU
leaves no device plane, so what ``run.py`` adds on a chip (the reduced
trace, the device's kind and memory peak) is a stand-in with kernel
events in it: whether the kernel's metric reads then rests on the
record alone, as it does on the chip."""

import argparse
import glob
import json
import os

import pytest

from benchmark import cells, training

TOY = os.path.join(cells.HERE, "tests", "toy")
DEVICE_PLANE = {"chips": 1, "window_s": 1.0, "busy_s": 0.9,
                "idle_share_worst": 0.1, "kernel_s": 0.2,
                "kernel_events": 12, "device_ops": [], "idle_gaps": []}


def _traced_record(cell, config, trace_dir):
    from benchmark import run as bench
    args = argparse.Namespace(seed=2 ** 31 + 33, seconds=1.0, trace=1)
    ctx = bench.Context(args, cell, config,
                        {"platform": "cpu", "kind": "cpu", "count": 1},
                        trace_dir)
    record = cells.role(cell["role"]).run(ctx)
    assert all(record["checks"].values()), record["checks"]
    record.update(trace=dict(DEVICE_PLANE), device_kind="TPU v5 lite",
                  memory_peak_bytes=10 ** 9)
    return record


@pytest.mark.parametrize("name,kernel", [("toy-lm-train", True),
                                         ("toy-glm-train", False)])
def test_listed_per_layer_names_are_the_names_the_readers_return(
        name, kernel, monkeypatch, tmp_path):
    monkeypatch.setattr(cells, "DATA_DIRS", [TOY, cells.HERE])
    cell = cells.load_cell(name)
    config = cells.load_config(cell["config"])
    listed = {m["name"] for m in cells.load_metrics(cell, end_to_end=False)}
    assert ("flash_roofline.train" in listed) is kernel
    record = _traced_record(cell, config, str(tmp_path))
    assert ("flash" in record) is kernel
    # every per-layer metric file, whatever it says of roles and
    # builders: the ones whose reader finds something in this record
    returned = set()
    for path in glob.glob(os.path.join(cells.HERE, "metrics", "*.json")):
        with open(path) as f:
            m = json.load(f)
        if m.get("end_to_end") or m["name"].endswith(".serve"):
            continue
        if cells.reader(m["reader"])(record, m) is not None:
            returned.add(m["name"])
    assert listed == returned


def test_the_job_names_adam_or_is_sgd_and_never_both():
    import bigdl_tpu.optim as optim
    sgd = training.optim_method({"learning_rate": 0.01, "momentum": 0.9})
    assert isinstance(sgd, optim.SGD)
    adam = training.optim_method(
        {"optim_method": {"name": "adam", "learning_rate": 0.002}})
    assert isinstance(adam, optim.Adam)
    with pytest.raises(SystemExit, match="which of them"):
        training.optim_method({"optim_method": {"name": "adam"},
                               "learning_rate": 0.01})
    with pytest.raises(SystemExit, match="known: 'adam'"):
        training.optim_method({"optim_method": {"name": "sgd"}})
