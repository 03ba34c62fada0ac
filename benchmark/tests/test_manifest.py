"""BENCHMARK.json says what the files say, and keeps to the contract's
limits that can be checked here."""

import json
import os
import re

from benchmark import cells, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _manifest():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_agrees_with_the_files():
    assert manifest.build(_manifest()) == _manifest()


def test_manifest_check_command_agrees(capsys):
    assert manifest.main(["--check"]) == 0
    assert "agrees" in capsys.readouterr().out


TRAIN = {"step_ms.train", "held_hbm_gb.train", "data_wait.train",
         "device_idle.train", "driver_busy.train", "step_trace_s",
         "step_compile_s"}


def _names(cell, end_to_end):
    return {m["name"] for m in cells.load_metrics(cell, end_to_end)}


def test_train_metrics_apply_by_role_whatever_the_configuration():
    """A train cell of either family the tree has, under a name no metric
    file lists, reports ``train_mfu`` and every ``*.train`` metric the day
    it lands: metrics apply by role, so the role stays ``lm_train``.  The
    kernel's metric is the one that asks the builder: a family whose
    ``attention_kernel`` is ``None`` is not listed under it, because its
    traced line could never hold it."""
    mix = {"batch": 1, "seq_len": 8192}
    dense = {"name": "a-cell-no-file-names", "role": "lm_train",
             "config": "opt-6.7b-l2", "mix": mix}
    latent = dict(dense, config="glm-5.2-l5-e16")
    for cell in (dense, latent):
        assert _names(cell, True) == {"train_mfu", "setup_s"}
    assert _names(dense, False) == TRAIN | {"flash_roofline.train"}
    assert _names(latent, False) == TRAIN
    # under a role of its own it would get none of them
    own = dict(latent, role="another_family_train")
    assert _names(own, True) == {"setup_s"}
    assert _names(own, False) == {"step_trace_s", "step_compile_s"}


def test_where_builder_gives_narrows_and_never_widens():
    """The key narrows what ``roles`` or ``workloads`` admit; it admits
    nothing of its own, and the builder is not asked for a cell the
    roles already turn away (an image configuration's has no such
    function)."""
    metric = {"roles": ["lm_train"], "where_builder_gives":
              "attention_kernel"}
    mix = {"batch": 2, "seq_len": 2048}
    cell = {"name": "c", "role": "lm_train", "config": "opt-6.7b-l2",
            "mix": mix}
    assert cells.metric_applies(metric, cell)
    assert not cells.metric_applies(metric, dict(cell, role="image_train"))
    assert not cells.metric_applies(
        metric, dict(cell, config="glm-5.2-l5-e16"))
    named = {"workloads": ["c"], "where_builder_gives": "attention_kernel"}
    assert cells.metric_applies(named, dict(cell, role="other"))
    assert not cells.metric_applies(
        named, dict(cell, role="other", config="glm-5.2-l5-e16"))


def test_contract_limits():
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert set(e2e) == {"train_mfu", "serve_tok_s", "itl_p95_ms", "setup_s"}
    cells_ = [w["name"] for w in m["workloads"]]
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == \
        len(cells_)
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(cells_) // 4)
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(cells.ROOT, c["file"]))
        assert c["name"] in {w["config"] for w in m["workloads"]}
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert x["moves"] in e2e and len(x["layer"]) <= 200
        # the metric it moves is reported wherever it is
        on = set(x.get("workloads", cells_))
        assert on <= set(e2e[x["moves"]].get("workloads", cells_))
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer one
    for name in cells_:
        got = [x["name"] for x in m["end_to_end"]
               if name in x.get("workloads", cells_)]
        assert "setup_s" in got and len(got) >= 2
        assert any(name in x.get("workloads", cells_)
                   for x in m["per_layer"])
    assert len(json.dumps(m)) < 64 * 1024


def test_file_names_keep_to_the_alphabet():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, _, files in os.walk(cells.HERE):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), cells.ROOT)
            assert ok.match(rel), rel
