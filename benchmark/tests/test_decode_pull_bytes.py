"""``decode_pull_bytes.serve``: what the reader gives with and without the
program's counter, which cells list it, and what the serving role's toy
rehearsal leaves for it to read."""

import json

import pytest

from benchmark import cells
from benchmark.tests.test_rehearsal import TOY

NAME = "decode_pull_bytes.serve"


def _metric():
    with open(f"{cells.HERE}/metrics/zpr29.{NAME}.json") as f:
        return json.load(f)


def test_reader_without_and_with_the_counter():
    """On the parent commit the registry has ``LM/decode_steps`` and no
    ``LM/decode_pulled_bytes``: asking makes an empty one and the metric
    is left out; with both it is bytes over steps."""
    from bigdl_tpu import telemetry
    read = cells.reader(_metric()["reader"])
    telemetry.REGISTRY.drop_prefix("LM/decode_")
    assert read({}, {}) is None
    telemetry.counter("LM/decode_steps").inc(500)
    assert read({}, {}) is None                     # the parent's state
    telemetry.counter("LM/decode_pulled_bytes").inc(500 * 76)
    assert read({}, {}) == 76.0
    telemetry.REGISTRY.drop_prefix("LM/decode_")
    telemetry.gauge("LM/decode_pulled_bytes").set(3.0)   # another kind
    telemetry.counter("LM/decode_steps").inc(2)
    assert read({}, {}) is None
    telemetry.REGISTRY.drop_prefix("LM/decode_")


def test_every_serving_cell_lists_it_and_no_other():
    with open(f"{cells.ROOT}/BENCHMARK.json") as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    serving = [w["name"] for w in manifest["workloads"]
               if cells.load_cell(w["name"])["role"] == "lm_serve_open_loop"]
    assert entry["workloads"] == serving and len(serving) >= 2
    assert entry["moves"] == "itl_p95_ms" and entry["unit"] == "B"
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
    the cell's per-layer metrics name this one, and the counters the
    served run left read ``4 x max_batch``: the step's tokens and nothing
    else crossed."""
    from benchmark import run
    from bigdl_tpu import telemetry
    cell = cells.load_cell("toy-lm-serve")
    listed = [m for m in cells.load_metrics(cell, end_to_end=False)
              if m["name"] == NAME]
    assert len(listed) == 1
    telemetry.REGISTRY.drop_prefix("LM/decode_")
    rc = run.main(["--workload", "toy-lm-serve", "--allow-cpu", "--seed",
                   str(2 ** 31 + 29), "--seconds", "2", "--trace", "0"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert telemetry.counter("LM/decode_steps").value > 0
    got = cells.reader(listed[0]["reader"])({}, listed[0])
    assert got == 4.0 * cell["job"]["engine"]["max_batch"]
