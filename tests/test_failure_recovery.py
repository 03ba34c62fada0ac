"""Failure recovery and checkpoint/resume.

Reference analogs: the retry-from-snapshot loop
(``optim/DistriOptimizer.scala:750-816``) and the fault-injection test style
(``optim/DistriOptimizerSpec.scala:89-99`` — a model that throws on
schedule).  Injection here is host-side (a transformer that fails once at a
given batch) because under jit the module Python only runs at trace time.
"""

import os

import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset import LocalDataSet, SampleToMiniBatch
from bigdl_tpu.dataset.dataset import ShardedDataSet
from bigdl_tpu.dataset.datasets import synthetic_separable
from bigdl_tpu.dataset.transformer import Transformer
from bigdl_tpu.optim.evaluator import Evaluator
from bigdl_tpu.utils import config, file_io


class FailOnce(Transformer):
    """Raises on the k-th batch it sees, once — a transient node failure."""

    def __init__(self, fail_at: int):
        self.fail_at = fail_at
        self.seen = 0
        self.tripped = False

    def __call__(self, it):
        for batch in it:
            self.seen += 1
            if self.seen == self.fail_at and not self.tripped:
                self.tripped = True
                raise RuntimeError("injected failure (simulated node loss)")
            yield batch


def _mlp(din, nclass, seed=5):
    import jax
    m = (nn.Sequential().add(nn.Linear(din, 16)).add(nn.Tanh())
         .add(nn.Linear(16, nclass)).add(nn.LogSoftMax()))
    m.reset(jax.random.PRNGKey(seed))
    return m


@pytest.fixture(autouse=True)
def _fast_retry():
    config.set_property("bigdl.failure.retryTimeInterval", 0.0)
    yield
    config.clear_property("bigdl.failure.retryTimeInterval")


class TestRetryFromCheckpoint:
    def test_recovers_from_injected_failure(self, tmp_path):
        samples = synthetic_separable(128, 4, n_classes=2, seed=3)
        injector = FailOnce(fail_at=9)
        ds = (LocalDataSet(samples).transform(SampleToMiniBatch(32))
              .transform(injector))
        model = _mlp(4, 2)
        opt = optim.Optimizer.create(model, ds, nn.ClassNLLCriterion())
        opt.set_optim_method(optim.SGD(learning_rate=0.5))
        opt.set_end_when(optim.max_epoch(8))
        opt.set_checkpoint(str(tmp_path / "ckpt"),
                           optim.several_iteration(2))
        trained = opt.optimize()

        assert injector.tripped, "injection never fired"
        acc = Evaluator(trained).test(
            samples, [optim.Top1Accuracy()], 32)[0][1].final_result()
        assert acc > 0.9, f"training did not recover from failure: acc={acc}"
        # counters continued rather than restarting from scratch
        assert opt.optim_method.state["evalCounter"] >= 8 * 4 - 2

    def test_gives_up_after_retry_budget(self, tmp_path):
        class AlwaysFail(Transformer):
            def __call__(self, it):
                for _ in it:
                    raise RuntimeError("permanent failure")
                yield  # pragma: no cover

        samples = synthetic_separable(64, 4, n_classes=2)
        ds = (LocalDataSet(samples).transform(SampleToMiniBatch(32))
              .transform(AlwaysFail()))
        opt = optim.Optimizer.create(_mlp(4, 2), ds, nn.ClassNLLCriterion())
        opt.set_end_when(optim.max_epoch(2))
        config.set_property("bigdl.failure.retryTimes", 3)
        try:
            with pytest.raises(RuntimeError, match="permanent failure"):
                opt.optimize()
        finally:
            config.clear_property("bigdl.failure.retryTimes")

    def test_argument_errors_not_retried(self):
        """The reference aborts immediately on IllegalArgumentException."""
        samples = synthetic_separable(64, 4, n_classes=2)
        ds = ShardedDataSet(samples, 4).transform(SampleToMiniBatch(32, 4))
        from bigdl_tpu.parallel import DistriOptimizer
        opt = DistriOptimizer(_mlp(4, 2), ds, nn.ClassNLLCriterion())
        with pytest.raises(ValueError, match="must match"):
            opt.optimize()  # mesh/partition mismatch: no retry loop


class TestSnapshotPairing:
    def test_latest_requires_model_optim_pair(self, tmp_path):
        """A crash between the ``model.N`` and ``optimMethod.N`` saves
        leaves a model-only snapshot: ``latest()`` must skip it and hand
        back the newest COMPLETE pair (regression — the old scan keyed on
        ``model.*`` alone and restore crashed on the missing optim)."""
        from bigdl_tpu.optim.optimizer import Checkpoint
        ckpt = Checkpoint(str(tmp_path), optim.every_epoch())
        ckpt.save(_mlp(4, 2), optim.SGD(learning_rate=0.1), 3)
        file_io.save(_mlp(4, 2), str(tmp_path / "model.7"))  # torn: no pair
        model_path, optim_path, n = ckpt.latest()
        assert n == 3
        assert model_path.endswith("model.3")
        # both halves load
        file_io.load(model_path)
        assert file_io.load(optim_path).state is not None

    def test_restore_falls_back_past_unloadable_snapshot(self, tmp_path):
        """``file_io.load`` failing on the newest snapshot must not kill
        the retry loop: restore walks to the next-older snapshot."""
        from bigdl_tpu.optim.optimizer import Checkpoint
        samples = synthetic_separable(64, 4, n_classes=2)
        ds = LocalDataSet(samples).transform(SampleToMiniBatch(32))
        opt = optim.Optimizer.create(_mlp(4, 2), ds, nn.ClassNLLCriterion())
        method = optim.SGD(learning_rate=0.1)
        method.state["evalCounter"] = 3
        opt.set_checkpoint(str(tmp_path), optim.every_epoch())
        opt.checkpoint.save(opt.model, method, 3)
        # newest snapshot: a complete legacy pair whose model pickle is
        # garbage (no manifest, so only the unpickler can catch it)
        (tmp_path / "model.9").write_bytes(b"\x80\x04 not a pickle")
        file_io.save(optim.SGD(learning_rate=0.1), str(tmp_path /
                                                       "optimMethod.9"))
        assert opt._restore_latest_checkpoint()
        assert opt.optim_method.state["evalCounter"] == 3


class TestRetryBackoff:
    def test_capped_exponential_with_jitter(self):
        from bigdl_tpu.optim.optimizer import _retry_backoff
        # jitter pinned at 1.0: pure capped exponential
        assert _retry_backoff(1, 2.0, 8.0, rand=1.0) == 2.0
        assert _retry_backoff(2, 2.0, 8.0, rand=1.0) == 4.0
        assert _retry_backoff(3, 2.0, 8.0, rand=1.0) == 8.0
        assert _retry_backoff(9, 2.0, 8.0, rand=1.0) == 8.0   # capped
        # a cap BELOW the base wins (operator asked for fast retries)...
        assert _retry_backoff(3, 120.0, 30.0, rand=1.0) == 30.0
        # ...and a non-positive cap means uncapped
        assert _retry_backoff(5, 2.0, 0.0, rand=1.0) == 32.0
        # jitter floor is half the interval
        assert _retry_backoff(3, 2.0, 8.0, rand=0.0) == 4.0
        # a zero base (the test fixture's config) never sleeps
        assert _retry_backoff(5, 0.0, 900.0) == 0.0
        # random jitter stays within [0.5, 1.0] x interval
        for _ in range(20):
            v = _retry_backoff(2, 2.0, 8.0)
            assert 2.0 <= v <= 4.0

    def test_sleeps_follow_backoff_with_patched_clock(self, tmp_path):
        """No real sleeping in tier-1: the retry loop's waits go through
        the injectable ``optimizer._sleep`` and must grow exponentially
        up to the cap."""
        from bigdl_tpu.optim import optimizer as optimizer_mod

        class AlwaysFail(Transformer):
            def __call__(self, it):
                for _ in it:
                    raise RuntimeError("permanent failure")
                yield  # pragma: no cover

        samples = synthetic_separable(64, 4, n_classes=2)
        ds = (LocalDataSet(samples).transform(SampleToMiniBatch(32))
              .transform(AlwaysFail()))
        opt = optim.Optimizer.create(_mlp(4, 2), ds, nn.ClassNLLCriterion())
        opt.set_end_when(optim.max_epoch(2))
        config.set_property("bigdl.failure.retryTimes", 4)
        config.set_property("bigdl.failure.retryTimeInterval", 2.0)
        config.set_property("bigdl.failure.maxRetryInterval", 4.0)
        slept = []
        orig = optimizer_mod._sleep
        optimizer_mod._sleep = slept.append
        try:
            with pytest.raises(RuntimeError, match="permanent failure"):
                opt.optimize()
        finally:
            optimizer_mod._sleep = orig
            for k in ("bigdl.failure.retryTimes",
                      "bigdl.failure.maxRetryInterval"):
                config.clear_property(k)
        # 4 attempts -> 3 waits; attempt a waits in
        # [0.5, 1.0] x min(2*2^(a-1), 4)
        assert len(slept) == 3, slept
        assert 1.0 <= slept[0] <= 2.0
        assert 2.0 <= slept[1] <= 4.0
        assert 2.0 <= slept[2] <= 4.0   # capped at maxRetryInterval

    def test_attempt_counter_resets_on_progress(self, tmp_path):
        """Mirrors the reference's retryNum reset: failures separated by
        real training progress must each start a fresh attempt budget —
        three spaced failures survive a retryTimes=2 budget that two
        back-to-back failures would exhaust."""

        class FailEvery(Transformer):
            """Trips once at each configured batch count."""

            def __init__(self, fail_ats):
                self.fail_ats = set(fail_ats)
                self.seen = 0
                self.trips = 0

            def __call__(self, it):
                for batch in it:
                    self.seen += 1
                    if self.seen in self.fail_ats:
                        self.fail_ats.discard(self.seen)
                        self.trips += 1
                        raise RuntimeError("injected repeated failure")
                    yield batch

        samples = synthetic_separable(128, 4, n_classes=2, seed=3)
        injector = FailEvery([6, 12, 18])
        ds = (LocalDataSet(samples).transform(SampleToMiniBatch(32))
              .transform(injector))
        opt = optim.Optimizer.create(_mlp(4, 2), ds, nn.ClassNLLCriterion())
        opt.set_optim_method(optim.SGD(learning_rate=0.5))
        opt.set_end_when(optim.max_epoch(8))
        opt.set_checkpoint(str(tmp_path / "ckpt"),
                           optim.several_iteration(2))
        config.set_property("bigdl.failure.retryTimes", 2)
        try:
            trained = opt.optimize()
        finally:
            config.clear_property("bigdl.failure.retryTimes")
        assert injector.trips == 3, "not every failure fired"
        acc = Evaluator(trained).test(
            samples, [optim.Top1Accuracy()], 32)[0][1].final_result()
        assert acc > 0.9, f"training did not recover: acc={acc}"


class TestKillAndResume:
    def test_resumed_run_matches_uninterrupted(self, tmp_path):
        """Train 2 epochs + checkpoint, 'kill', resume from snapshot for 2
        more — final weights match an uninterrupted 4-epoch run exactly
        (shuffles disabled via fixed index order: LocalDataSet shuffles use
        the global RNG, so both runs see identical batch order per epoch)."""
        from bigdl_tpu.utils.random_generator import RandomGenerator

        samples = synthetic_separable(128, 4, n_classes=2, seed=7)

        def fresh_ds():
            return LocalDataSet(samples).transform(SampleToMiniBatch(128))

        # uninterrupted 4 epochs (full-batch: order-independent)
        model_a = _mlp(4, 2, seed=11)
        opt_a = optim.Optimizer.create(model_a, fresh_ds(),
                                       nn.ClassNLLCriterion())
        opt_a.set_optim_method(optim.SGD(learning_rate=0.3, momentum=0.9))
        opt_a.set_end_when(optim.max_epoch(4))
        opt_a.optimize()
        w_a, _ = model_a.get_parameters()

        # interrupted: 2 epochs, checkpoint, then resume in a NEW optimizer
        model_b = _mlp(4, 2, seed=11)
        opt_b = optim.Optimizer.create(model_b, fresh_ds(),
                                       nn.ClassNLLCriterion())
        opt_b.set_optim_method(optim.SGD(learning_rate=0.3, momentum=0.9))
        opt_b.set_end_when(optim.max_epoch(2))
        opt_b.set_checkpoint(str(tmp_path / "ckpt"), optim.every_epoch())
        opt_b.optimize()

        latest = opt_b.checkpoint.latest()
        assert latest is not None
        model_c = file_io.load(latest[0])
        optim_c = optim.OptimMethod.load(latest[1])
        assert optim_c.state["epoch"] >= 2

        opt_c = optim.Optimizer.create(model_c, fresh_ds(),
                                       nn.ClassNLLCriterion())
        opt_c.set_optim_method(optim_c)
        opt_c.set_end_when(optim.max_epoch(4))
        trained = opt_c.optimize()
        w_c, _ = trained.get_parameters()

        np.testing.assert_allclose(np.asarray(w_c), np.asarray(w_a),
                                   rtol=1e-4, atol=1e-6)


class TestRemoteCheckpointIntegration:
    """Integration-grade remote persistence (reference tags real-HDFS/S3
    integration suites, ``integration/HdfsSpec.scala``): the FULL
    train -> checkpoint -> crash -> retry-from-snapshot cycle against a
    remote fsspec filesystem (memory:// — the scheme this image can
    actually host; hdfs://, s3://, gs:// route through the identical
    code path, differing only in the installed client)."""

    def _clean(self):
        import fsspec
        fs = fsspec.filesystem("memory")
        if fs.exists("/bigdl_it"):
            fs.rm("/bigdl_it", recursive=True)

    def test_checkpoint_roundtrip_over_remote_scheme(self):
        self._clean()
        samples = synthetic_separable(128, 4, n_classes=2, seed=3)
        ds = LocalDataSet(samples).transform(SampleToMiniBatch(32))
        model = _mlp(4, 2)
        opt = optim.Optimizer.create(model, ds, nn.ClassNLLCriterion())
        opt.set_optim_method(optim.SGD(learning_rate=0.5))
        opt.set_end_when(optim.max_epoch(3))
        opt.set_checkpoint("memory://bigdl_it/ckpt", optim.every_epoch())
        trained = opt.optimize()

        latest = opt.checkpoint.latest()
        assert latest is not None
        model_path, optim_path, n = latest
        assert model_path.startswith("memory://")
        reloaded = file_io.load(model_path)
        x = np.stack([s.feature for s in samples[:16]])
        np.testing.assert_allclose(
            np.asarray(reloaded.evaluate().forward(x)),
            np.asarray(trained.evaluate().forward(x)),
            rtol=1e-6)
        # optim snapshot round-trips with its counters
        ro = file_io.load(optim_path)
        assert ro.state["evalCounter"] > 0

    def test_retry_restores_from_remote_snapshot(self):
        self._clean()
        samples = synthetic_separable(128, 4, n_classes=2, seed=3)
        injector = FailOnce(fail_at=9)
        ds = (LocalDataSet(samples).transform(SampleToMiniBatch(32))
              .transform(injector))
        model = _mlp(4, 2)
        opt = optim.Optimizer.create(model, ds, nn.ClassNLLCriterion())
        opt.set_optim_method(optim.SGD(learning_rate=0.5))
        opt.set_end_when(optim.max_epoch(8))
        opt.set_checkpoint("memory://bigdl_it/retry",
                           optim.several_iteration(2))
        trained = opt.optimize()
        assert injector.tripped
        acc = Evaluator(trained).test(
            samples, [optim.Top1Accuracy()], 32)[0][1].final_result()
        assert acc > 0.9, f"remote-checkpoint recovery failed: acc={acc}"

    def test_overwrite_false_guard_applies_remotely(self):
        self._clean()
        file_io.save({"v": 1}, "memory://bigdl_it/obj")
        with pytest.raises(FileExistsError):
            file_io.save({"v": 2}, "memory://bigdl_it/obj",
                         overwrite=False)
        assert file_io.load("memory://bigdl_it/obj")["v"] == 1

    def test_temp_sweep_is_age_gated(self, tmp_path):
        """Checkpoint.save must not reclaim a RECENT foreign temp (it may
        be another live writer's in-flight atomic write); an hour-old
        orphan from a hard-killed writer IS swept."""
        import time
        from bigdl_tpu.optim.optimizer import Checkpoint
        ckpt = Checkpoint(str(tmp_path), optim.every_epoch())
        fresh = tmp_path / "model.9.tmp_bigdl.4242.deadbeef"
        stale = tmp_path / "model.8.tmp_bigdl.4243.cafebabe"
        fresh.write_bytes(b"live writer in flight")
        stale.write_bytes(b"orphan")
        old = time.time() - Checkpoint.TEMP_SWEEP_AGE_S - 60
        os.utime(stale, (old, old))
        ckpt.save(_mlp(4, 2), optim.SGD(learning_rate=0.1), 1)
        assert fresh.exists(), "recent foreign temp was swept"
        assert not stale.exists(), "hour-old orphan survived the sweep"
        # and neither ever pollutes latest()
        _, _, n = ckpt.latest()
        assert n == 1

    def test_remote_temp_sweep_is_age_gated(self):
        """The age gate must work through the fsspec modified() branch of
        file_io.modified_time, not just local getmtime: a backdated
        memory:// orphan is swept, a fresh one survives."""
        import datetime
        import fsspec
        from bigdl_tpu.optim.optimizer import Checkpoint
        self._clean()
        root = "memory://bigdl_it/sweep"
        ckpt = Checkpoint(root, optim.every_epoch())
        fs = fsspec.filesystem("memory")
        fs.makedirs("/bigdl_it/sweep", exist_ok=True)
        for name in ("model.9.tmp_bigdl.77.aa", "model.8.tmp_bigdl.78.bb"):
            with fs.open(f"/bigdl_it/sweep/{name}", "wb") as f:
                f.write(b"x")
        fs.store["/bigdl_it/sweep/model.8.tmp_bigdl.78.bb"].modified = (
            datetime.datetime.now(datetime.timezone.utc)
            - datetime.timedelta(seconds=Checkpoint.TEMP_SWEEP_AGE_S + 60))
        assert file_io.modified_time(
            root + "/model.8.tmp_bigdl.78.bb") is not None
        ckpt.save(_mlp(4, 2), optim.SGD(learning_rate=0.1), 1)
        names = file_io.listdir(root)
        assert "model.9.tmp_bigdl.77.aa" in names, names
        assert "model.8.tmp_bigdl.78.bb" not in names, names
        _, _, n = ckpt.latest()
        assert n == 1

    def test_partial_remote_write_never_selected_as_latest(self):
        """Atomic remote saves: a crashed in-flight temp must neither be
        picked by latest() nor survive as a final object."""
        import fsspec
        from bigdl_tpu.optim.optimizer import Checkpoint
        self._clean()
        ckpt = Checkpoint("memory://bigdl_it/atomic", optim.every_epoch())
        m = _mlp(4, 2)
        ckpt.save(m, optim.SGD(learning_rate=0.1), 3)
        # simulate a crash mid-write of snapshot 7
        fs = fsspec.filesystem("memory")
        with fs.open("/bigdl_it/atomic/model.7.tmp_bigdl", "wb") as f:
            f.write(b"truncated")
        model_path, _, n = ckpt.latest()
        assert n == 3 and model_path.endswith("model.3")
        reloaded = file_io.load(model_path)
        x = np.zeros((1, 4), np.float32)
        assert np.asarray(reloaded.forward(x)).shape == (1, 2)


class TestUnhealableFailuresNotRetried:
    """What a snapshot restore cannot change re-raises at once: the
    backoff (120 s, 240 s, ... by default) is for faults a restore can
    heal, and sleeping through it only hides the real error."""

    def _opt(self):
        samples = synthetic_separable(64, 4, n_classes=2)
        ds = LocalDataSet(samples).transform(SampleToMiniBatch(32))
        opt = optim.Optimizer.create(_mlp(4, 2), ds, nn.ClassNLLCriterion())
        opt.set_end_when(optim.max_iteration(2))
        return opt

    def test_invalid_argument_runtime_error_reraises_without_sleep(
            self, monkeypatch):
        import jax
        from bigdl_tpu.optim import optimizer as optimizer_mod
        slept, calls = [], []
        monkeypatch.setattr(optimizer_mod, "_sleep", slept.append)
        opt = self._opt()

        def boom():
            calls.append(1)
            raise jax.errors.JaxRuntimeError(
                "INVALID_ARGUMENT: Expected args to "
                "execute_sharded_on_local_devices to have 8 shards")
        monkeypatch.setattr(opt, "_optimize", boom)
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="INVALID_ARGUMENT"):
            opt.optimize()
        assert calls == [1] and slept == []

    def test_compile_phase_failure_reraises_without_sleep(self, monkeypatch):
        from bigdl_tpu.optim import optimizer as optimizer_mod
        from bigdl_tpu.utils.compile_cache import CachedStep
        slept, calls = [], []
        monkeypatch.setattr(optimizer_mod, "_sleep", slept.append)

        def rejected(self, *a, **kw):
            calls.append(1)
            raise RuntimeError("RESOURCE_EXHAUSTED: XLA:TPU compile "
                               "permanent error. Ran out of memory")
        monkeypatch.setattr(CachedStep, "_compile_entry_tagged", rejected)
        with pytest.raises(RuntimeError, match="compile permanent error"):
            self._opt().optimize()
        assert calls == [1] and slept == []

    def test_other_runtime_errors_still_back_off(self, monkeypatch):
        """The control: an unclassified runtime failure keeps its
        retry budget and its backoff."""
        from bigdl_tpu.optim import optimizer as optimizer_mod
        slept = []
        monkeypatch.setattr(optimizer_mod, "_sleep", slept.append)
        opt = self._opt()
        monkeypatch.setattr(
            opt, "_optimize",
            lambda: (_ for _ in ()).throw(RuntimeError("link flapped")))
        config.set_property("bigdl.failure.retryTimes", 3)
        try:
            with pytest.raises(RuntimeError, match="link flapped"):
                opt.optimize()
        finally:
            config.clear_property("bigdl.failure.retryTimes")
        assert len(slept) == 2
