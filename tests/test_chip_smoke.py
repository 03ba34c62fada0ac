"""CPU rehearsal of ``chip_smoke.py``: the same phase functions at toy
width with flash off, the device gate's two refusals, and the exit code
of a phase that raises.  The real run needs the chip."""

import json

import pytest

import chip_smoke

TOY = dict(chip_smoke.FULL_WIDTH,
           vocab=64, d_model=32, n_head=2, n_layers=2, max_len=64,
           flash=False, batch=4, seq_len=16, steps=4,
           max_batch=2, max_context=32, prefill_buckets="8,32",
           prompt_lens=(3, 5, 9, 12), max_new_tokens=4,
           deadline_ms=60000.0, parity_prompt=5, parity_tokens=3,
           dp_batch=8, dp_steps=3)

FAKE_DEVICE = {"platform": "rehearsal", "kind": "cpu", "count": 8}


@pytest.fixture
def no_cache_side_effect(monkeypatch, tmp_path):
    # the helper then sets nothing in code, and jax read the
    # environment long ago: the rehearsal leaves no cache behind
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_every_phase_at_toy_width(monkeypatch, capsys, no_cache_side_effect,
                                  tmp_path):
    monkeypatch.setattr(chip_smoke, "device_gate", lambda: FAKE_DEVICE)
    assert chip_smoke.main(TOY) == 0
    out = capsys.readouterr().out.strip().splitlines()
    # the last line is the verdict and holds nothing else; the summary
    # of the run is the line before it
    assert json.loads(out[-1]) == {"ok": True, "device": FAKE_DEVICE}
    summary = json.loads(out[-2])
    assert summary["ok"] is True and summary["device"] == FAKE_DEVICE
    assert summary["compile_cache_dir"] == str(tmp_path)
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert {"wall_s", "peak_bytes_in_use"} <= set(summary)
    train, serve, dp = summary["train"], summary["serve"], summary["dp"]
    assert {"wall_s", "first_loss", "last_loss", "compiles", "warmup_ms",
            "trace_ms", "compile_ms", "warmup_other_ms",
            "audit_peak_bytes"} <= set(train)
    assert train["last_loss"] < train["first_loss"]
    assert train["compiles"] == 1
    assert {"wall_s", "warmup_s", "compile_ms", "compiles",
            "tokens_served", "parity_agree"} <= set(serve)
    assert serve["requests"] == 4 and serve["tokens_served"] >= 16
    # serving compiles what it dispatches and nothing else: decode, the
    # KV scrub, one prefill per bucket (lm_full only for the parity pair)
    assert serve["compiles"]["lm_decode"] == 1
    assert serve["compiles"]["lm_kv_scrub"] == 1
    assert serve["compiles"]["lm_prefill"] == 2
    assert dp["devices"] == 4 and dp["last_loss"] < dp["first_loss"]


def test_gate_refuses_a_cpu_platform(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "")
    with pytest.raises(SystemExit, match="not a TPU"):
        chip_smoke.device_gate()


def test_gate_refuses_a_virtual_device_request(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=8")
    with pytest.raises(SystemExit, match="XLA_FLAGS"):
        chip_smoke.device_gate()


def test_main_is_nonzero_at_the_gate_and_prints_no_result(
        capsys, no_cache_side_effect):
    assert chip_smoke.main(TOY) != 0
    assert "{" not in capsys.readouterr().out


def test_a_phase_that_raises_makes_main_nonzero(monkeypatch, capsys,
                                                no_cache_side_effect):
    monkeypatch.setattr(chip_smoke, "device_gate", lambda: FAKE_DEVICE)

    def boom(model, cfg):
        raise AssertionError("loss did not fall")
    monkeypatch.setattr(chip_smoke, "train_phase", boom)
    assert chip_smoke.main(TOY) == 1
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out and "skipped" not in captured.out
    assert "loss did not fall" in captured.err
