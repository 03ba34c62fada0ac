"""``decode_ahead_share.serve``: what the reader gives with and without the
program's counter, which cells list it, and what the serving role's toy
rehearsal leaves for it to read."""

import json

import pytest

from benchmark import cells
from benchmark.tests.test_rehearsal import TOY

NAME = "decode_ahead_share.serve"


def _metric():
    with open(f"{cells.HERE}/metrics/zpr32.{NAME}.json") as f:
        return json.load(f)


def test_reader_without_and_with_the_counter():
    """On the parent commit the registry has ``LM/decode_steps`` and no
    ``LM/decode_steps_ahead``: asking makes an empty one and the metric is
    left out; with both it is the share, in percent."""
    from bigdl_tpu import telemetry
    read = cells.reader(_metric()["reader"])
    telemetry.REGISTRY.drop_prefix("LM/decode_")
    assert read({}, {}) is None
    telemetry.counter("LM/decode_steps").inc(400)
    assert read({}, {}) is None                     # the parent's state
    telemetry.counter("LM/decode_steps_ahead").inc(394)
    assert read({}, {}) == 98.5
    telemetry.REGISTRY.drop_prefix("LM/decode_")
    telemetry.gauge("LM/decode_steps_ahead").set(3.0)    # another kind
    telemetry.counter("LM/decode_steps").inc(4)
    assert read({}, {}) is None
    telemetry.REGISTRY.drop_prefix("LM/decode_")


def test_every_serving_cell_lists_it_and_no_other():
    with open(f"{cells.ROOT}/BENCHMARK.json") as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert manifest["per_layer"][-1] is entry       # appended, not inserted
    serving = [w["name"] for w in manifest["workloads"]
               if cells.load_cell(w["name"])["role"] == "lm_serve_open_loop"]
    assert entry["workloads"] == serving and len(serving) >= 2
    assert entry["moves"] == "itl_p95_ms" and entry["unit"] == "%"
    assert entry["better"] == "higher"
    assert entry["source"] == "program_counter"
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] == "decode_ema_ms.serve"}
    assert entry["layer"] in layers


@pytest.fixture
def toy_dirs():
    cells.DATA_DIRS.insert(0, TOY)
    yield
    cells.DATA_DIRS.remove(TOY)


def test_toy_rehearsal_of_the_serving_role_lists_and_feeds_it(toy_dirs,
                                                              capsys):
    """The role at toy widths, in this process, as ``run.py`` drives it:
    the cell's per-layer metrics name this one, and most of the decode
    steps of the served run were dispatched ahead of the previous step's
    pull; none of its rows was wasted (the traffic has no ``eos_id``)."""
    from benchmark import run
    from bigdl_tpu import telemetry
    cell = cells.load_cell("toy-lm-serve")
    listed = [m for m in cells.load_metrics(cell, end_to_end=False)
              if m["name"] == NAME]
    assert len(listed) == 1
    telemetry.REGISTRY.drop_prefix("LM/decode_")
    rc = run.main(["--workload", "toy-lm-serve", "--allow-cpu", "--seed",
                   str(2 ** 31 + 32), "--seconds", "2", "--trace", "0"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    steps = telemetry.counter("LM/decode_steps").value
    ahead = telemetry.counter("LM/decode_steps_ahead").value
    assert 0 < ahead < steps
    got = cells.reader(listed[0]["reader"])({}, listed[0])
    assert got == 100.0 * ahead / steps and got > 50.0
    assert telemetry.counter("LM/decode_rows_wasted").value == 0
