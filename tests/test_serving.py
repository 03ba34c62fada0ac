"""Overload-tolerant serving: the request-path robustness contract.

The claims under test (ISSUE 9 acceptance criteria): admission control
rejects fast with a structured ``Overloaded`` instead of letting tail
latency collapse; expired requests are shed at dequeue time before
wasting a device slot; a poison request is quarantined alone while its
batch survives; a hung dispatch is aborted by the watchdog with
diagnosis and the engine cools down; SIGTERM drains gracefully and
rejects late arrivals retriably — and through ALL of it, every submitted
request terminates with exactly one outcome (the accounting identity),
non-poison results are bit-identical to a clean ``Predictor.predict``,
and the strict retrace sentinel stays at zero across ragged arrival
patterns.
"""

import time

import numpy as np
import pytest

import jax

import bigdl_tpu.nn as nn
from bigdl_tpu import telemetry
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.optim.predictor import Predictor
from bigdl_tpu.serving import (HungDispatchError, Overloaded, ServingDataError,
                               ServingEngine, run_open_loop)
from bigdl_tpu.serving.engine import DeadlineExceeded, OUTCOMES, \
    ServingInfraError
from bigdl_tpu.utils import chaos, config, elastic

DIN, DOUT = 4, 3

_SERVING_KEYS = (
    "bigdl.compile.buckets", "bigdl.serving.warmupBatches",
    "bigdl.chaos.slowRequestAt", "bigdl.chaos.poisonRequestAt",
    "bigdl.chaos.hangDispatchAt", "bigdl.chaos.burstArrivals",
)


@pytest.fixture(autouse=True)
def _serving_env():
    """Disarmed chaos, cleared preemption, clean knobs around every
    test."""
    elastic.clear_preemption()
    yield
    chaos.uninstall()
    elastic.clear_preemption()
    for k in _SERVING_KEYS:
        config.clear_property(k)


def _model(seed=7):
    m = (nn.Sequential().add(nn.Linear(DIN, 16)).add(nn.Tanh())
         .add(nn.Linear(16, DOUT)))
    m.reset(jax.random.PRNGKey(seed))
    return m


def _engine(model=None, buckets="2,4,8", warm=True, **kw):
    if buckets:
        config.set_property("bigdl.compile.buckets", buckets)
    model = model if model is not None else _model()
    eng = ServingEngine(model, **kw)
    if warm:
        eng.warmup(np.zeros((DIN,), np.float32))
    return eng


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, DIN)).astype(np.float32)


def _assert_identity(stats_or_rec):
    assert stats_or_rec["unaccounted"] == 0, stats_or_rec
    total = sum(stats_or_rec[o] for o in OUTCOMES)
    assert total == stats_or_rec["submitted"], stats_or_rec


# ---------------------------------------------------------------------------
# Predictor / evaluator empty-dataset satellites
# ---------------------------------------------------------------------------

class TestEmptyDataset:
    def test_predict_empty_dataset_returns_empty_ndarray(self):
        out = Predictor(_model()).predict([])
        assert isinstance(out, np.ndarray)
        assert out.shape == (0,)

    def test_predict_empty_sample_stream(self):
        from bigdl_tpu.dataset.dataset import LocalDataSet
        out = Predictor(_model()).predict(LocalDataSet([]))
        assert isinstance(out, np.ndarray) and out.size == 0

    def test_predict_class_empty_dataset(self):
        out = Predictor(_model()).predict_class([])
        assert isinstance(out, np.ndarray)
        assert out.shape == (0,)
        assert np.issubdtype(out.dtype, np.integer)

    def test_evaluate_dataset_empty_raises_clear_error(self):
        import bigdl_tpu.optim as optim
        from bigdl_tpu.optim.evaluator import evaluate_dataset
        with pytest.raises(ValueError, match="empty dataset"):
            evaluate_dataset(_model(), [], [optim.Top1Accuracy()])


# ---------------------------------------------------------------------------
# The happy path: micro-batching with Predictor parity
# ---------------------------------------------------------------------------

class TestServingBasics:
    def test_results_bit_identical_to_predictor(self):
        x = _rows(11)
        with _engine(deadline_ms=10000.0) as eng:
            handles = [eng.submit(x[i]) for i in range(len(x))]
            got = np.stack([h.result(timeout=30) for h in handles])
            ref = Predictor(eng.model).predict([MiniBatch(x)])
            np.testing.assert_array_equal(got, ref)
            stats = eng.stats()
        _assert_identity(stats)
        assert stats["completed"] == len(x)

    def test_ragged_arrivals_zero_retraces(self):
        """Dribbled arrivals make ragged batch occupancies; every one
        pads to the bucket plan, so the STRICT sentinel (armed for all
        tier-1 tests) sees zero post-warmup retraces."""
        x = _rows(9, seed=3)
        with _engine(deadline_ms=10000.0, max_batch=4) as eng:
            handles = []
            for i in range(len(x)):
                handles.append(eng.submit(x[i]))
                if i % 3 == 0:
                    time.sleep(0.03)     # let occupancy vary
            for h in handles:
                h.result(timeout=30)
            assert eng.sentinel is not None
            assert eng.sentinel.retraces == 0
            assert eng.batches >= 2
            _assert_identity(eng.stats())

    def test_metrics_exported_through_registry(self):
        x = _rows(6)
        with _engine(deadline_ms=10000.0) as eng:
            for h in [eng.submit(r) for r in x]:
                h.result(timeout=30)
        snap = telemetry.REGISTRY.snapshot()
        assert snap["counters"]["Serving/completed"] >= 6
        assert "Serving/p99_ms" in snap["gauges"]
        assert "Serving/latency_ms" in snap["histograms"]
        assert snap["histograms"]["Serving/batch_occupancy"]["count"] >= 1
        prom = telemetry.REGISTRY.prometheus_text()
        assert "Serving_latency_ms" in prom
        assert "Serving_queue_depth" in prom


# ---------------------------------------------------------------------------
# Admission control: reject at the door
# ---------------------------------------------------------------------------

class TestAdmission:
    def test_queue_full_rejects_fast_and_structured(self):
        eng = _engine(warm=False, start=False, max_queue_depth=4,
                      deadline_ms=10000.0)
        try:
            for i in range(4):
                eng.submit(_rows(1)[0])
            t0 = time.monotonic()
            with pytest.raises(Overloaded) as ei:
                eng.submit(_rows(1)[0])
            reject_ms = (time.monotonic() - t0) * 1e3
            assert reject_ms < 50, "reject must be fast, at the door"
            e = ei.value
            assert e.retriable
            assert e.reason == "queue full"
            assert e.queue_depth == 4 and e.max_depth == 4
        finally:
            eng.stop()
        stats = eng.stats()
        _assert_identity(stats)
        assert stats["rejected"] == 1
        assert stats["shed"] == 4        # never-started engine sheds on stop

    def test_projected_wait_rejection(self):
        """With a warmed service-time EMA, admission rejects a request
        whose projected queue wait already blows its deadline budget —
        reject-at-the-door instead of queueing it to die."""
        eng = _engine(warm=False, start=False, max_batch=2,
                      max_queue_depth=64, deadline_ms=100.0)
        try:
            eng._ema.ema = 500.0         # 500 ms per batch, observed
            with pytest.raises(Overloaded) as ei:
                eng.submit(_rows(1)[0])
            assert ei.value.reason == "projected wait"
            assert ei.value.projected_wait_ms >= 500.0
            assert ei.value.retriable
            # a generous per-request deadline CAN still be admitted
            h = eng.submit(_rows(1)[0], deadline_ms=60000.0)
            assert h.index == 0
        finally:
            eng.stop()
        _assert_identity(eng.stats())

    def test_stopped_engine_rejects_closed(self):
        eng = _engine(warm=False, start=False)
        eng.stop()
        with pytest.raises(Overloaded) as ei:
            eng.submit(_rows(1)[0])
        assert ei.value.reason == "closed"
        _assert_identity(eng.stats())


# ---------------------------------------------------------------------------
# Deadline shedding at dequeue
# ---------------------------------------------------------------------------

class TestDeadlineShedding:
    def test_slow_request_sheds_expired_behind_it(self):
        """chaos.slowRequestAt wedges the first handled request for
        0.5 s; everything queued behind it ages past its 120 ms deadline
        and must be shed at DEQUEUE time — cheap, structured, before any
        device work."""
        config.set_property("bigdl.chaos.slowRequestAt", "1:0.5")
        chaos.install()
        x = _rows(4)
        with _engine(deadline_ms=120.0, max_batch=4) as eng:
            handles = [eng.submit(r) for r in x]
            out = []
            for h in handles:
                try:
                    out.append(("ok", h.result(timeout=30)))
                except DeadlineExceeded as e:
                    assert e.retriable
                    assert e.waited_ms > e.deadline_ms
                    out.append(("shed", None))
            stats = eng.stats()
        _assert_identity(stats)
        kinds = [k for k, _ in out]
        assert kinds[0] == "ok", "the slow request itself still completes"
        assert kinds.count("shed") == 3, kinds
        assert stats["shed"] == 3 and stats["completed"] == 1


# ---------------------------------------------------------------------------
# Poison quarantine: the PR 7 taxonomy on the request path
# ---------------------------------------------------------------------------

class TestPoisonQuarantine:
    def test_chaos_poison_fails_one_keeps_batch_alive(self):
        config.set_property("bigdl.chaos.poisonRequestAt", "1")
        chaos.install()
        x = _rows(4, seed=5)
        with _engine(deadline_ms=10000.0, max_batch=4) as eng:
            handles = [eng.submit(r) for r in x]
            ref = Predictor(eng.model).predict([MiniBatch(x)])
            for i, h in enumerate(handles):
                if h.index == 1:
                    with pytest.raises(ServingDataError):
                        h.result(timeout=30)
                    assert h.outcome == "quarantined"
                else:
                    np.testing.assert_array_equal(h.result(timeout=30),
                                                  ref[i])
            stats = eng.stats()
        _assert_identity(stats)
        assert stats["quarantined"] == 1
        assert stats["completed"] == 3

    def test_ill_shaped_payload_quarantined_without_chaos(self):
        x = _rows(3, seed=6)
        with _engine(deadline_ms=10000.0, max_batch=4) as eng:
            good = [eng.submit(r) for r in x]
            bad = eng.submit(np.zeros((DIN + 2,), np.float32))
            with pytest.raises(ServingDataError, match="ill-shaped"):
                bad.result(timeout=30)
            for h in good:
                assert h.result(timeout=30).shape == (DOUT,)
            stats = eng.stats()
        _assert_identity(stats)
        assert stats["quarantined"] == 1 and stats["completed"] == 3

    def test_non_numeric_payload_quarantined(self):
        with _engine(deadline_ms=10000.0) as eng:
            h = eng.submit(np.array(["not", "numbers", "at", "all"]))
            with pytest.raises(ServingDataError):
                h.result(timeout=30)
            assert h.outcome == "quarantined"
        _assert_identity(eng.stats())


# ---------------------------------------------------------------------------
# Hung-dispatch watchdog
# ---------------------------------------------------------------------------

class TestHungDispatch:
    def test_watchdog_aborts_wedged_dispatch_with_diagnosis(self):
        fired_before = telemetry.counter("Serving/watchdog_fired").value
        config.set_property("bigdl.chaos.hangDispatchAt", "5:3.0")
        # the watchdog's first heartbeat covers setup (skipped), the
        # next 2 are warmup observations, and the EMA seeds from their
        # minimum at the one after: 4 dispatches arm detection
        config.set_property("bigdl.serving.warmupBatches", 2)
        chaos.install()
        with _engine(deadline_ms=30000.0, max_batch=2, stall_factor=5.0,
                     cooldown_batches=2) as eng:
            # dispatches 1-4 seed the EMA from the warmup MINIMUM (the
            # PR 5 seeding — a slow first dispatch cannot poison it)
            for _ in range(4):
                eng.submit(_rows(1)[0]).result(timeout=30)
            t0 = time.monotonic()
            victim = eng.submit(_rows(1)[0])
            with pytest.raises(HungDispatchError, match="wedged past"):
                victim.result(timeout=30)
            abort_s = time.monotonic() - t0
            assert victim.outcome == "shed"
            assert abort_s < 3.0, \
                "the abort must land well before the 3 s wedge expires"
            # the engine re-admits (cooldown clears when the backlog is
            # empty) and keeps serving
            deadline = time.monotonic() + 10
            while True:
                try:
                    h = eng.submit(_rows(1)[0])
                    break
                except Overloaded as e:
                    assert e.reason in ("cooldown",), e
                    assert time.monotonic() < deadline
                    time.sleep(0.02)
            assert h.result(timeout=30).shape == (DOUT,)
            stats = eng.stats()
        _assert_identity(stats)
        assert stats["shed"] == 1 and stats["completed"] == 5
        assert telemetry.counter("Serving/watchdog_fired").value == \
            fired_before + 1
        assert telemetry.REGISTRY.snapshot()["gauges"][
            "Serving/watchdog_detect_ms"] >= 0

    def test_cooldown_gates_admission_until_backlog_clears(self):
        with _engine(deadline_ms=10000.0) as eng:
            with eng._lock:
                eng._cooldown = 5
            with pytest.raises(Overloaded) as ei:
                eng.submit(_rows(1)[0])
            assert ei.value.reason == "cooldown" and ei.value.retriable
            # empty backlog: the batcher's next idle poll re-admits
            deadline = time.monotonic() + 5
            while True:
                try:
                    h = eng.submit(_rows(1)[0])
                    break
                except Overloaded:
                    assert time.monotonic() < deadline
                    time.sleep(0.02)
            assert h.result(timeout=30).shape == (DOUT,)
        _assert_identity(eng.stats())


# ---------------------------------------------------------------------------
# Graceful drain (SIGTERM / stop)
# ---------------------------------------------------------------------------

class TestGracefulDrain:
    def test_preemption_drains_inflight_and_rejects_late_arrivals(self):
        x = _rows(6, seed=8)
        with _engine(deadline_ms=30000.0, max_batch=2) as eng:
            handles = [eng.submit(r) for r in x]
            elastic.request_preemption(reason="test SIGTERM")
            # admission must close within one batcher poll
            deadline = time.monotonic() + 5
            rejected = None
            while rejected is None:
                try:
                    eng.submit(x[0])
                    assert time.monotonic() < deadline
                    time.sleep(0.005)
                except Overloaded as e:
                    rejected = e
            assert rejected.reason in ("draining", "closed")
            assert rejected.retriable, \
                "late arrivals carry the retriable marker"
            # everything admitted before the signal still completes
            for h in handles:
                assert h.result(timeout=30).shape == (DOUT,)
            stats = eng.stats()
        assert stats["completed"] >= len(x)
        _assert_identity(eng.stats())

    def test_stop_sheds_undrainable_backlog_retriably(self):
        """A backlog that can never dispatch (the batcher was never
        started) is shed with a retriable infra error when the engine
        goes down — never silently dropped."""
        eng = _engine(warm=False, start=False, deadline_ms=30000.0)
        handles = [eng.submit(r) for r in _rows(3)]
        eng.stop()
        for h in handles:
            with pytest.raises(ServingInfraError, match="draining"):
                h.result(timeout=1)
            assert h.outcome == "shed"
        stats = eng.stats()
        _assert_identity(stats)
        assert stats["shed"] == 3


# ---------------------------------------------------------------------------
# Open-loop load generation + burstArrivals
# ---------------------------------------------------------------------------

class TestLoadGenerator:
    def test_burst_arrivals_injector_accounted(self):
        config.set_property("bigdl.chaos.burstArrivals", "2:5")
        chaos.install()
        x = _rows(6, seed=9)
        with _engine(deadline_ms=30000.0, max_batch=8,
                     max_queue_depth=64) as eng:
            rec = run_open_loop(eng, list(x), rate_hz=0.0, seed=1)
        assert rec["submitted"] == 6 + 5     # the herd rode on position 2
        _assert_identity(rec)
        assert rec["completed"] == 11
        # burst copies carry the same payload: their results match the
        # scheduled arrival's
        for j in range(5):
            np.testing.assert_array_equal(rec["results"][f"2+b{j}"],
                                          rec["results"]["2"])

    def test_open_loop_poisson_under_capacity_all_complete(self):
        x = _rows(20, seed=10)
        with _engine(deadline_ms=30000.0, max_batch=8) as eng:
            rec = run_open_loop(eng, list(x), rate_hz=300.0, seed=2)
        _assert_identity(rec)
        assert rec["completed"] == 20
        assert len(rec["latency_ms"]) == 20


# ---------------------------------------------------------------------------
# The combined chaos proof (ISSUE 9 acceptance criterion)
# ---------------------------------------------------------------------------

class TestCombinedChaosPlan:
    def test_poison_plus_hang_plus_sigterm_exact_accounting(self):
        """One plan, three fault classes, mid-load: a poison request, a
        hung dispatch, and a SIGTERM.  Every submitted request ends in
        exactly one of the four outcomes, non-poison completions match a
        clean Predictor.predict over the same inputs, and the strict
        retrace sentinel stays at zero across the ragged batches the
        faults leave behind.

        The match is to float32 rounding, not bitwise: the reference is
        one 24-row program and the engine serves the same rows through
        1-to-4-row bucket programs, and XLA does not promise that two
        separately compiled batch shapes round a row identically
        (jaxlib 0.9.0's CPU backend differs by 1 ulp)."""
        config.set_property("bigdl.chaos.poisonRequestAt", "6")
        config.set_property("bigdl.chaos.hangDispatchAt", "5:1.0")
        config.set_property("bigdl.serving.warmupBatches", 2)
        chaos.install()
        x = _rows(24, seed=11)
        ref = None

        def on_arrival(i):
            if i == 16:
                elastic.request_preemption(reason="combined-plan SIGTERM")
            elif i == 17:
                # give the batcher one beat to observe the signal, so
                # the tail of the load really arrives AFTER admission
                # closed (the late-arrival contract under test)
                time.sleep(0.4)

        with _engine(deadline_ms=30000.0, max_batch=4, stall_factor=5.0,
                     cooldown_batches=2, grace_period=20.0) as eng:
            # dispatches 1-3 seed the watchdog EMA (2 warmup
            # observations past the skipped setup heartbeat); admission
            # indices 0-2 are theirs, so poison position 6 lands
            # mid-load and the hang (dispatch 5) lands post-seed
            for _ in range(3):
                eng.submit(x[0]).result(timeout=30)
            ref = Predictor(eng.model).predict([MiniBatch(x)])
            rec = run_open_loop(eng, list(x), rate_hz=400.0, seed=3,
                                on_arrival=on_arrival)
            sentinel = eng.sentinel
            stats = eng.stats()

        # -- exact accounting: nothing vanished, nothing double-counted
        _assert_identity(rec)
        _assert_identity(stats)
        assert all(h is None or h.outcome in OUTCOMES
                   for _, h in rec["handles"])

        # -- the poison request was quarantined alone
        assert rec["quarantined"] == 1
        poisoned = [e for e in rec["errors"].values()
                    if isinstance(e, ServingDataError)]
        assert len(poisoned) == 1

        # -- the hung dispatch was aborted with diagnosis; its victims
        #    were shed retriably
        hung = [e for e in rec["errors"].values()
                if isinstance(e, HungDispatchError)]
        assert len(hung) >= 1, "the wedged batch must fail diagnosed"
        assert all(e.retriable for e in hung)

        # -- SIGTERM closed admission: late arrivals rejected retriably
        assert rec["rejected"] >= 1
        rejections = [e for e in rec["errors"].values()
                      if isinstance(e, Overloaded)]
        assert rejections and all(e.retriable for e in rejections)

        # -- non-poison completions: the clean batch Predictor's rows
        assert rec["completed"] >= 5
        for key, out in rec["results"].items():
            idx = int(key.split("+")[0])
            np.testing.assert_allclose(out, ref[idx], rtol=1e-6, atol=1e-6)

        # -- zero post-warmup retraces across all the ragged batches
        assert sentinel is not None and sentinel.retraces == 0


# ---------------------------------------------------------------------------
# Restart/reuse contract + supervisor abandon (ISSUE 17 satellites)
# ---------------------------------------------------------------------------

class TestStopContract:
    def test_stop_is_idempotent_and_terminal(self):
        eng = _engine()
        h = eng.submit(_rows(1)[0])
        h.result(timeout=5.0)
        eng.stop()
        assert eng.terminal and not eng.batcher_alive()
        eng.stop()          # second stop: a quiet no-op, never a raise
        eng.stop(grace=0.0)
        assert eng.terminal
        _assert_identity(eng.stats())

    def test_post_stop_submit_is_structured_retriable(self):
        eng = _engine()
        eng.stop()
        for _ in range(3):  # stable across repeats, not half-torn state
            with pytest.raises(Overloaded) as ei:
                eng.submit(_rows(1)[0])
            assert ei.value.reason == "closed"
            assert ei.value.retriable
        _assert_identity(eng.stats())

    def test_restart_after_stop_raises_structured(self):
        eng = _engine()
        eng.stop()
        with pytest.raises(ServingInfraError, match="terminal"):
            eng.start()
        # the refusal did not corrupt the terminal state
        assert eng.terminal
        _assert_identity(eng.stats())

    def test_lifecycle_introspection(self):
        eng = _engine()
        assert not eng.terminal and not eng.draining
        assert eng.batcher_alive() and not eng.crashed()
        assert isinstance(eng.batcher_ident(), int)
        assert eng.queue_depth() == 0
        eng.stop()
        assert eng.terminal and not eng.batcher_alive()
        assert not eng.crashed()    # orderly stop is not a crash

    def test_abandon_sheds_once_and_releases_governor_bytes(self):
        from bigdl_tpu.resources import GOVERNOR
        eng = _engine(start=False)          # batcher never runs: the
        h = eng.submit(_rows(1)[0])         # handle stays in flight
        acct = GOVERNOR.account("serving_admission")
        charged = h.payload_nbytes
        assert charged > 0
        before = acct.nbytes
        assert h.abandon(reason="replica_crash") is True
        assert h.outcome == "shed"
        assert h.payload_nbytes == 0
        assert acct.nbytes == before - charged
        with pytest.raises(ServingInfraError, match="abandoned"):
            h.result(timeout=0)
        # terminal states are first-wins: a second abandon is a no-op
        assert h.abandon() is False
        assert acct.nbytes == before - charged
        # abandon moves the outcome to the SUPERVISOR'S ledger (the
        # fleet counts it as shed; tests/test_fleet.py asserts that
        # identity) — the engine's own counts see the handle as
        # stranded, and the later engine-side shed is a first-wins
        # no-op, never a double count
        assert eng.stats()["unaccounted"] == 1
        eng.stop()
        assert eng.stats()["unaccounted"] == 1
        assert eng.stats()["shed"] == 0

    def test_abandon_loses_to_completion(self):
        eng = _engine()
        h = eng.submit(_rows(1)[0])
        out = h.result(timeout=5.0)
        assert h.abandon() is False         # already completed: no-op
        assert h.outcome == "completed"
        np.testing.assert_array_equal(out, h.result(timeout=0))
        eng.stop()
        _assert_identity(eng.stats())
