"""``latent_fetch_ratio.serve``: what the reader gives on counters shaped
like a step that selects and gathers for every slot and like one that
loops over the live slots, and without the counters; which cell lists it;
and what the serving role's toy rehearsal of the second family leaves
for it to read."""

import json

import pytest

from benchmark import cells
from benchmark.tests.test_rehearsal import TOY

NAME = "latent_fetch_ratio.serve"


def _metric():
    with open(f"{cells.HERE}/metrics/zpr36.{NAME}.json") as f:
        return json.load(f)


def _fresh():
    from bigdl_tpu import telemetry
    telemetry.REGISTRY.drop_prefix("LM/kv_rows_fetched")
    telemetry.REGISTRY.drop_prefix("LM/attn_")
    return telemetry


@pytest.mark.parametrize("live,fetched_slots,want", [
    ([3, 5, 4, 2], [16, 16, 16, 16], 64 / 14),     # every slot, live or not
    ([3, 5, 4, 2], [8, 8, 8, 8], 32 / 14),         # eight slots a trip
    ([0, 1], [2, 2], 4.0)], ids=["every_slot", "live_slots", "idle"])
def test_reader_is_rows_fetched_over_rows_used(live, fetched_slots, want):
    """A step of the cell's 16 slots and 2,048 selected rows, over four
    steps at 2-5 live slots: the step that reads every slot gives 16 over
    the mean live, the loop over live slots its chunks over the same."""
    telemetry = _fresh()
    read = cells.reader(_metric()["reader"])
    for n, slots in zip(live, fetched_slots):
        telemetry.counter("LM/kv_rows_fetched").inc(slots * 2048)
        telemetry.counter("LM/attn_selected_tokens").inc(n * 2048)
    assert read({}, {}) == pytest.approx(want)
    _fresh()


def test_reader_without_the_counters():
    """A program that never served (or from before either counter) has
    neither: asking makes empty ones and the metric is left out; one
    without the other is left out too."""
    telemetry = _fresh()
    read = cells.reader(_metric()["reader"])
    assert read({}, {}) is None
    telemetry.counter("LM/attn_selected_tokens").inc(2048)
    assert read({}, {}) is None
    _fresh()
    telemetry.counter("LM/kv_rows_fetched").inc(2048)
    assert read({}, {}) is None
    _fresh()


def test_the_glm_cell_lists_it_and_no_other():
    with open(f"{cells.ROOT}/BENCHMARK.json") as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == ["glm52-serve-longdoc-r80"]
    assert entry["moves"] == "itl_p95_ms" and entry["better"] == "lower"
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] == "sparse_kept_share.serve"}
    assert entry["layer"] in layers


@pytest.fixture
def toy_dirs():
    cells.DATA_DIRS.insert(0, TOY)
    yield
    cells.DATA_DIRS.remove(TOY)


def test_toy_rehearsal_of_the_glm_serving_role_feeds_it(toy_dirs, capsys):
    """The role at toy widths, in this process, as ``run.py`` drives it
    (the metric lists the accepted cell by name, not the toy): the
    counters the served run left read at least 1 (no step fetched fewer
    rows than its live slots used) and under the toy's four slots."""
    from benchmark import run
    cell = cells.load_cell("toy-glm-serve")
    _fresh()
    rc = run.main(["--workload", "toy-glm-serve", "--allow-cpu", "--seed",
                   str(2 ** 31 + 36), "--seconds", "2", "--trace", "0"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    got = cells.reader(_metric()["reader"])({}, _metric())
    assert 1.0 <= got < cell["job"]["engine"]["max_batch"]
