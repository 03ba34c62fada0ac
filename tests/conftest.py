"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's multi-node-without-a-cluster simulation
(``optim/DistriOptimizerSpec.scala:38-40``: Engine.init(4 nodes) over
local[1]): here 8 virtual XLA host devices play 8 TPU chips so sharding and
collectives run for real without hardware.

Must set env vars BEFORE jax is imported anywhere.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import numpy as np
import pytest

# Tests leave no compile cache behind: the drivers under test call
# engine.init_compile_cache(), which would otherwise point XLA's
# persistent cache at <checkout>/.jax_cache for the rest of the session.
jax.config.update("jax_enable_compilation_cache", False)

# Numerical-parity tests need full fp32 matmuls; the framework's production
# default stays backend-default (bf16 passes on the MXU — the TPU-first choice).
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    yield


@pytest.fixture(autouse=True)
def _sanitizers_armed():
    """Arm analysis passes 1-2 in STRICT mode for every tier-1 test: a
    post-warmup retrace of any fused step raises RetraceError, and an
    implicit device→host sync inside the optimizer hot loop raises
    HostSyncError with its call-site.  This makes the sanitizers a
    standing CI contract — any change that reintroduces signature drift
    or a stray float()/np.asarray in the hot loop fails the suite, not a
    production run three weeks later."""
    from bigdl_tpu.utils import config

    config.set_property("bigdl.analysis.retrace", "strict")
    config.set_property("bigdl.analysis.hostSync", "strict")
    # the HLO program auditor, strict for every tier-1 compile: any
    # fused step whose lowered program breaks its declared collective
    # contract, drifts precision, or blows its layout budget raises
    # ProgramContractError at warmup
    config.set_property("bigdl.audit.collectives", "strict")
    config.set_property("bigdl.audit.precision", "strict")
    config.set_property("bigdl.audit.memory", "strict")
    yield
    config.clear_property("bigdl.analysis.retrace")
    config.clear_property("bigdl.analysis.hostSync")
    for k in ("collectives", "precision", "memory"):
        config.clear_property(f"bigdl.audit.{k}")


@pytest.fixture(autouse=True)
def _lock_witness_armed():
    """Arm the runtime lock witness STRICT for every tier-1 test: any
    lock acquisition that closes a cycle in the process-wide
    acquisition-order graph raises LockOrderViolation (both sites, both
    stacks) BEFORE the blocking acquire — the suite fails on a deadlock
    that never had to happen this run.  Graph and counters are dropped
    after each test so one test's acquisition order can never poison
    another's."""
    from bigdl_tpu.analysis import lockwitness
    from bigdl_tpu.utils import config

    config.set_property("bigdl.analysis.lockWitness", "strict")
    lockwitness.arm()
    yield
    lockwitness.disarm()
    lockwitness.reset()
    config.clear_property("bigdl.analysis.lockWitness")


@pytest.fixture(scope="session")
def cold_probe(tmp_path_factory):
    """The compile probe's cold start (``tests/compile_probe.py``): a
    real child process trains and evaluates against an empty executable
    cache.  Returns ``(cache dir, what the child printed)``; run once a
    worker, for whichever of its tests ask (the warm-start test and the
    offline-audit tests share it where xdist puts their files on one
    worker)."""
    import compile_probe
    cache_dir = str(tmp_path_factory.mktemp("probe_ccache"))
    return cache_dir, compile_probe.run_child(cache_dir)


@pytest.fixture(autouse=True, scope="session")
def _no_thread_leaks():
    """End-of-suite leak check: every framework thread spawned during the
    run must be gone (joined or daemonized-and-idle) by session end.  A
    non-daemon thread still alive here means some stop()/close() path
    forgot a join — exactly the class of bug the concurrency pass exists
    to keep out — and it would hang the interpreter at exit."""
    import threading

    baseline = {t.ident for t in threading.enumerate()}
    yield
    # stragglers get one grace join: a worker mid-teardown on a loaded
    # CI box is latency, not a leak
    leaked = [t for t in threading.enumerate()
              if t.ident not in baseline and not t.daemon and t.is_alive()]
    for t in leaked:
        t.join(timeout=5.0)
    leaked = [t for t in leaked if t.is_alive()]
    assert not leaked, (
        "non-daemon threads leaked past session end (missing join in a "
        "stop()/close() path):\n" + "\n".join(
            f"  - {t.name} (ident={t.ident}, daemon={t.daemon})"
            for t in leaked))


@pytest.fixture(autouse=True)
def _telemetry_armed():
    """Arm the span tracer for EVERY tier-1 test: telemetry must be able
    to ride along any training run without changing its behaviour — in
    particular, with the strict host-sync guard above also armed, a
    traced train proves the tracer itself introduces zero device→host
    syncs.  Rings are small (memory stays flat across the session) and
    dropped after each test."""
    from bigdl_tpu import telemetry

    telemetry.arm(ring_size=4096)
    yield
    telemetry.disarm()
    telemetry.reset_tracer()


@pytest.fixture(autouse=True)
def _forensics_isolated():
    """Per-test isolation for the forensic layers: request traces and
    the incident event ring are dropped after each test, and automatic
    incident-bundle dumps are disabled (a chaos test shedding requests
    must not litter incident-*.json into the CWD — tests that assert on
    bundles opt back in or call incident.dump() themselves)."""
    from bigdl_tpu.telemetry import incident, request_trace
    from bigdl_tpu.utils import config

    config.set_property("bigdl.incident.autoDump", False)
    yield
    request_trace.disarm()
    request_trace.reset()
    incident.reset()
    config.clear_property("bigdl.incident.autoDump")


@pytest.fixture(autouse=True)
def _hang_guard(request):
    """Per-test hard timeout without pytest-timeout (not installed in
    this image): SIGALRM fails the test at 1200 s — generous enough for
    the 2-OS-process multihost legs compiling under full-suite CPU
    contention, small enough that a genuine deadlock fails the run
    instead of wedging it.  pytest's built-in ``faulthandler_timeout``
    (pytest.ini, 900 s) dumps all stacks first, so a kill always comes
    with a diagnosis."""
    import signal

    if os.name != "posix":  # pragma: no cover
        yield
        return

    def on_alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded the 1200 s hang guard: {request.node.nodeid}")

    prev = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(1200)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run tests marked slow (multihost subprocess legs, model-zoo "
             "builds); deselected by default so `pytest -q` stays fast")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip = pytest.mark.skip(reason="slow: opt in with --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
