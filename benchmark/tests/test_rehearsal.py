"""Every role end to end at toy widths on the CPU, through ``run.py`` as
the driver calls it, and the proof that without a TPU it measures
nothing."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import cells

TOY = os.path.join(cells.HERE, "tests", "toy")


def _run(args, devices=1, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if devices > 1:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "run.py")] + args,
        cwd=cells.ROOT, env=env, capture_output=True, text=True,
        timeout=timeout)


@pytest.mark.parametrize("cell,devices,seconds", [
    ("toy-lm-train", 1, 2), ("toy-lm-serve", 1, 3),
    ("toy-lm-train-dp", 4, 2), ("toy-resnet-train", 1, 6),
    # a second family under the same role, as new files only: Adam from
    # the job, the builder's count, no flash shapes
    ("toy-glm-train", 1, 2)])
def test_role_rehearsal(cell, devices, seconds):
    r = _run(["--workload", cell, "--data-dir", TOY, "--allow-cpu",
              "--seed", str(2 ** 31 + 77), "--seconds", str(seconds),
              "--trace", "0"], devices)
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    # a number from a CPU run is never written under a device metric's name
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert line["not_a_measurement"]["allow_cpu"] is True
    assert "setup_s" in line["rehearsal_values"]


def test_without_a_tpu_nothing_is_measured():
    r = _run(["--workload", "opt67b-train-t2048", "--seed", "1",
              "--seconds", "1", "--trace", "0"])
    assert r.returncode != 0
    assert "not a TPU" in r.stderr
    assert not any(l.startswith("{") for l in r.stdout.splitlines())


def test_virtual_devices_are_refused():
    r = _run(["--workload", "toy-lm-train-dp", "--data-dir", TOY,
              "--seed", "1", "--seconds", "1", "--trace", "0"], devices=4)
    assert r.returncode != 0
    assert not any(l.startswith("{") for l in r.stdout.splitlines())
