"""The ``glm_moe_dsa`` builder, reference, cell shape and per-layer readers
end to end at toy widths on the CPU, through ``run.py`` as
``test_rehearsal.py`` drives the other roles."""

import json

import pytest

from benchmark.tests.test_rehearsal import TOY, _run


def test_glm_serve_rehearsal():
    r = _run(["--workload", "toy-glm-serve", "--data-dir", TOY,
              "--allow-cpu", "--seed", str(2 ** 31 + 77), "--seconds", "3",
              "--trace", "0"])
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {}
    assert "setup_s" in line["rehearsal_values"]
    assert "float32" in r.stdout and "experts_held" in r.stdout
    # the builder held the model to the configuration's precision first
    assert "served log-probs vs reference" in r.stdout


@pytest.fixture(scope="module")
def toy_glm():
    from benchmark import cells
    from benchmark.builders.common import seed_key
    cells.DATA_DIRS.insert(0, TOY)
    try:
        config = cells.load_config("toy-glm")
    finally:
        cells.DATA_DIRS.remove(TOY)
    gate = config["program"].pop("served_logprob_gate")
    model = cells.builder(config["builder"]).build(config, seed_key(7),
                                                   on_tpu=False)
    config["program"]["served_logprob_gate"] = gate
    return model, config


def test_served_logprobs_tell_the_precision_below(toy_glm):
    """The limit lies between its two readings: the program under it, the
    reference with its matrices rounded to 8 bits over it."""
    from benchmark import served_logprobs
    model, config = toy_glm
    tol = config["program"]["served_logprob_gate"]["tolerance"]
    got = served_logprobs.compare(model, config, [7, 26],
                                  control="float8_e4m3fn")
    assert got["decode_steps"] == 7
    assert got["max_abs_dlogp_over_steps"]["max"] < tol / 10
    assert got["control_max_abs_dlogp_over_steps"]["median"] > 10 * tol


def test_a_model_over_the_tolerance_is_not_measured(toy_glm):
    import copy

    from benchmark import served_logprobs
    from benchmark.builders.common import seed_key
    model, config = toy_glm
    assert served_logprobs.gate(model, config, seed_key(7))[
        "max_abs_dlogp_over_steps"]["median"] < 1e-4
    tight = copy.deepcopy(config)
    tight["program"]["served_logprob_gate"]["tolerance"] = 1e-9
    with pytest.raises(SystemExit, match="not the reference"):
        served_logprobs.gate(model, tight, seed_key(7))


def test_counter_readers_find_nothing_in_a_program_without_them():
    """On the parent commit the registry has no such counter: asking for
    one makes an empty one, and the readers leave their metrics out."""
    from benchmark import cells
    from bigdl_tpu import telemetry
    for name in ("LM/attn_", "LM/moe_", "LM/prompt_tokens",
                 "LM/weight_bytes", "LM/pool_bytes"):
        telemetry.REGISTRY.drop_prefix(name)
    for name in ("sparse_kept_share", "experts_held_share",
                 "prefill_ktok_s", "engine_hbm_gb"):
        assert cells.reader(name)({}, {}) is None
    telemetry.gauge("LM/weight_bytes").set(7_000_000_000)
    telemetry.gauge("LM/pool_bytes").set(2_500_000_000)
    assert cells.reader("engine_hbm_gb")({}, {}) == 9.5
    telemetry.counter("LM/attn_context_tokens").inc(200)
    telemetry.counter("LM/attn_selected_tokens").inc(50)
    assert cells.reader("sparse_kept_share")({}, {}) == 25.0
