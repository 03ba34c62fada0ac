"""``dot_weight_gb.serve``: what the reader gives with and without the
program's gauge, which cells list it, and what the serving role's toy
rehearsal leaves for it to read."""

import json

import pytest

from benchmark import cells
from benchmark.tests.test_rehearsal import TOY

NAME = "dot_weight_gb.serve"


def _metric():
    with open(f"{cells.HERE}/metrics/zpr38.{NAME}.json") as f:
        return json.load(f)


def _fresh():
    from bigdl_tpu import telemetry
    telemetry.REGISTRY.drop_prefix("LM/dot_weight_bytes")
    return telemetry


def test_reader_without_and_with_the_gauge():
    """A program from before the gauge has none: asking makes an empty
    one and the metric is left out; with it, bytes in GB (the chat cell's
    608.6M matrix weights at 2 bytes)."""
    telemetry = _fresh()
    read = cells.reader(_metric()["reader"])
    assert read({}, {}) is None
    telemetry.gauge("LM/dot_weight_bytes").set(2 * 608_632_832)
    assert read({}, {}) == pytest.approx(1.217265664)
    _fresh()
    telemetry.counter("LM/dot_weight_bytes").inc(5)     # another kind
    assert read({}, {}) is None
    _fresh()


def test_both_serving_cells_list_it_beside_the_pool_metrics():
    with open(f"{cells.ROOT}/BENCHMARK.json") as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    serving = [w["name"] for w in manifest["workloads"]
               if cells.load_cell(w["name"])["role"] == "lm_serve_open_loop"]
    assert sorted(entry["workloads"]) == sorted(serving)
    assert entry["moves"] == "itl_p95_ms" and entry["better"] == "lower"
    assert entry["unit"] == "GB"
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] == "cache_bytes_per_token.serve"}
    assert entry["layer"] in layers


@pytest.fixture
def toy_dirs():
    cells.DATA_DIRS.insert(0, TOY)
    yield
    cells.DATA_DIRS.remove(TOY)


def test_toy_rehearsal_of_the_serving_role_feeds_it(toy_dirs, capsys):
    """The role at toy widths, in this process, as ``run.py`` drives it
    (the metric lists the accepted cells by name, not the toy): the gauge
    the engine left is its matrix weights at the width it holds them,
    float32 on the CPU."""
    from benchmark import run
    telemetry = _fresh()
    rc = run.main(["--workload", "toy-lm-serve", "--allow-cpu", "--seed",
                   str(2 ** 31 + 38), "--seconds", "2", "--trace", "0"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    cfg = cells.load_config(cells.load_cell("toy-lm-serve")["config"])
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    matrix = layers * (4 * d * d + 2 * d * cfg["ffn_dim"]) \
        + d * cfg["vocab_size"]
    assert telemetry.gauge("LM/dot_weight_bytes").value == 4 * matrix
    got = cells.reader(_metric()["reader"])({}, _metric())
    assert got == pytest.approx(4 * matrix / 1e9)
