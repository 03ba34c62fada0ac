"""The five set-up readers: what each gives from a filled registry, that
each gives ``None`` from a registry without the program's histograms (a
parent from before them), and that every cell is listed under them."""

import json

import pytest

from benchmark import cells

NAMES = ("compile_jaxpr_s", "compile_key_s", "compile_cache_io_s",
         "cache_hit_share", "program_setup_s")


def _read(name):
    with open(f"{cells.HERE}/metrics/zpr37.{name}.json") as f:
        metric = json.load(f)
    return cells.reader(metric["reader"])({}, metric)


@pytest.fixture
def empty():
    from bigdl_tpu import telemetry
    for prefix in ("Compile/", "Setup/"):
        telemetry.REGISTRY.drop_prefix(prefix)
    yield telemetry
    for prefix in ("Compile/", "Setup/"):
        telemetry.REGISTRY.drop_prefix(prefix)


def test_nothing_to_read_gives_none(empty):
    assert {n: _read(n) for n in NAMES} == dict.fromkeys(NAMES)


def test_values_from_a_filled_registry(empty):
    def phase(name, *values):
        h = empty.histogram("Compile/phase_ms", labels={"phase": name})
        for v in values:
            h.observe(v)

    phase("jaxpr", 1500.0, 500.0)
    phase("lower", 700.0, 300.0)
    phase("key", 250.0, 250.0)
    phase("compile", 4000.0)
    phase("load", 900.0)
    phase("audit", 10.0, 10.0)
    for entry, ms in (("lm_construct", 3000.0), ("lm_warmup", 9000.0),
                      ("lm_warmup", 1000.0)):
        empty.histogram("Setup/program_ms",
                        labels={"entry": entry}).observe(ms)
    assert _read("compile_jaxpr_s") == pytest.approx(2.0)
    assert _read("compile_key_s") == pytest.approx(0.5)
    assert _read("compile_cache_io_s") == 0.0    # timed, no cache file
    assert _read("cache_hit_share") == pytest.approx(50.0)
    assert _read("program_setup_s") == pytest.approx(13.0)
    phase("read", 120.0)
    phase("store", 80.0)
    empty.histogram("Compile/xla_cache_read_ms").observe(300.0)
    assert _read("compile_cache_io_s") == pytest.approx(0.5)


def test_a_cold_run_reads_zero_warm_share(empty):
    empty.histogram("Compile/phase_ms",
                    labels={"phase": "compile"}).observe(10.0)
    assert _read("cache_hit_share") == 0.0


def test_every_cell_is_listed_under_them():
    with open(f"{cells.ROOT}/BENCHMARK.json") as f:
        manifest = json.load(f)
    order = [m["name"] for m in manifest["per_layer"]]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    # appended after every metric accepted before them
    assert min(order.index(n) for n in NAMES) > order.index(
        "latent_fetch_ratio.serve")
    for name in NAMES:
        entry = entries[name]
        assert "workloads" not in entry and entry["moves"] == "setup_s"
        assert entry["source"] == "program_counter"
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] == "step_trace_s"}
    assert {entries[n]["layer"] for n in NAMES[:4]} == layers
