"""Resilient compilation: persistent executable cache, AOT warmup under a
watchdog, and shape-bucketed execution (ISSUE 8).

The contract under test (utils/compile_cache.py):

- a second trainer over the same model+topology reaches its first device
  step with ZERO fresh compiles (cache hit per fused step) and
  bit-identical step results: in this process, and in a second OS
  process over the first one's directory (``tests/compile_probe.py``);
- torn / uncommitted / corrupt / version-skewed / foreign-topology
  entries are a logged MISS and a recompile — never a crash — with
  exact numerical parity after the fallback;
- a wedged compile is detected within ``bigdl.compile.timeoutSec``,
  aborted with a diagnosed ``CompileTimeoutError``, and the trainer's
  retry loop restores-and-retries it like a divergence;
- with ``bigdl.compile.buckets`` configured, ragged validation/predict
  batches hit only pre-compiled signatures — proven by the PR 4 strict
  retrace sentinel observing zero post-warmup retraces.
"""

import json
import os

import numpy as np
import jax
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.optim.optimizer import Optimizer
from bigdl_tpu.utils import chaos, compile_cache, config
from bigdl_tpu.utils.compile_cache import (CachedStep, CompileCache,
                                           CompileTimeoutError,
                                           backend_fingerprint, bucket_size,
                                           pad_batch, slice_rows,
                                           tracked_jit)
from bigdl_tpu import telemetry

import compile_probe


@pytest.fixture
def cache_dir(tmp_path):
    d = str(tmp_path / "ccache")
    config.set_property("bigdl.compile.cacheDir", d)
    yield d
    config.clear_property("bigdl.compile.cacheDir")


@pytest.fixture(autouse=True)
def _no_lock_sleep(monkeypatch):
    monkeypatch.setattr(compile_cache, "_sleep", lambda s: None)
    yield


def _counter(name):
    return telemetry.REGISTRY.counter(name).value


def _pin_shuffle():
    """Training determinism across two runs in one process: the dataset
    shuffle draws from the thread-local generator."""
    from bigdl_tpu.utils.random_generator import RandomGenerator
    RandomGenerator.RNG().set_seed(1234)


def _samples(n=64, dim=8, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    return [Sample(rng.normal(size=(dim,)).astype(np.float32),
                   np.int64(i % classes + 1)) for i in range(n)]


def _trainer(samples, iterations=6):
    m = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.Tanh())
         .add(nn.Linear(16, 3)).add(nn.LogSoftMax()))
    m.reset(jax.random.PRNGKey(7))
    o = Optimizer.create(m, samples, nn.ClassNLLCriterion(), batch_size=16)
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    o.set_end_when(optim.max_iteration(iterations))
    return o, m


def _flat(params):
    return np.concatenate([np.ravel(np.asarray(x))
                           for x in jax.tree_util.tree_leaves(params)])


def _cached_of(o):
    step = o._step_fn
    return getattr(step, "__wrapped__", step)


# ---------------------------------------------------------------------------
# store lifecycle
# ---------------------------------------------------------------------------

class TestStoreLifecycle:
    def test_cold_miss_write_commit(self, cache_dir):
        """A cold run compiles, stores a committed entry (payload +
        manifest + commit marker, in that order), and counts a miss."""
        samples = _samples()
        _pin_shuffle()
        o, m = _trainer(samples)
        o.optimize()
        cached = _cached_of(o)
        assert cached.compiles == 1 and cached.cache_misses == 1
        assert cached.cache_hits == 0
        names = sorted(os.listdir(cache_dir))
        keys = {n.rsplit(".", 1)[0] for n in names if n != "lock"}
        assert len(keys) == 1
        key = keys.pop()
        assert {f"{key}.bin", f"{key}.json", f"{key}.commit"} <= set(names)
        with open(os.path.join(cache_dir, f"{key}.json")) as f:
            manifest = json.load(f)
        assert manifest["label"] == "local"
        # payloads checksum at C speed with the algo recorded (the PR 2
        # helper — the pure-Python crc32c walk would cost seconds per
        # multi-MB executable on the very path the cache accelerates)
        from bigdl_tpu.utils.checkpoint_manager import payload_checksum
        assert manifest["algo"] == payload_checksum(b"")[0]
        assert manifest["fingerprint"] == backend_fingerprint()
        assert manifest["topology"]["step"] == "local"
        assert manifest["bytes"] == os.path.getsize(
            os.path.join(cache_dir, f"{key}.bin"))

    def test_warm_hit_bit_identical(self, cache_dir):
        """The warm-start contract: a SECOND trainer (fresh step object,
        as a new process would build) loads the executable instead of
        compiling and trains to bit-identical weights."""
        samples = _samples()
        _pin_shuffle()
        o1, m1 = _trainer(samples)
        o1.optimize()
        _pin_shuffle()
        o2, m2 = _trainer(samples)
        o2.optimize()
        cached = _cached_of(o2)
        assert cached.cache_hits == 1, "warm start must load, not compile"
        assert cached.compiles == 0 and cached.cache_misses == 0
        assert np.array_equal(_flat(m1.params), _flat(m2.params)), \
            "warm-start step results must be bit-identical to cold"

    def test_second_process_starts_warm(self, cold_probe):
        """The same contract across REAL processes: a second child over
        the cold child's directory (``tests/compile_probe.py``: trainer
        plus ragged bucketed validation) compiles nothing, loads every
        signature the cold one compiled, trains to the same weights,
        and neither retraces its eval forward."""
        cache_dir, cold = cold_probe
        warm = compile_probe.run_child(cache_dir)

        def total(rec, key):
            return sum(step[key] for step in rec["steps"].values())

        assert total(cold, "hits") == 0 and total(cold, "misses") >= 3
        assert total(warm, "misses") == 0 and total(warm, "compiles") == 0, \
            "warm start must skip compilation entirely"
        assert total(warm, "hits") == total(cold, "misses"), \
            "every cold-compiled fused-step signature must warm-load"
        assert warm["weights_crc"] == cold["weights_crc"], \
            "warm-start step results must be bit-identical"
        assert cold["eval_retraces"] == 0 and warm["eval_retraces"] == 0, \
            "bucketed ragged validation must stay retrace-free"

    def test_loaded_executable_failing_first_dispatch_recompiles(
            self, cache_dir, monkeypatch):
        """Deserializing proves the bytes, not that the runtime will run
        them.  A load that ignores the entry's device list comes back
        bound to all 8 virtual devices (jax 0.9.0's default) and its
        first dispatch raises INVALID_ARGUMENT — which must degrade to
        a fresh compile like a failed deserialize, count a cache error,
        and train to the cold run's weights."""
        from jax.experimental import serialize_executable as se
        real = se.deserialize_and_load
        monkeypatch.setattr(
            se, "deserialize_and_load",
            lambda payload, in_tree, out_tree, **kw:
                real(payload, in_tree, out_tree))
        samples = _samples()
        _pin_shuffle()
        o1, m1 = _trainer(samples)
        o1.optimize()
        errors_before = _counter("Compile/cache_errors")
        _pin_shuffle()
        o2, m2 = _trainer(samples)
        o2.optimize()
        cached = _cached_of(o2)
        assert cached.cache_hits == 1 and cached.compiles == 1
        assert _counter("Compile/cache_errors") == errors_before + 1
        assert np.array_equal(_flat(m1.params), _flat(m2.params))

    def test_corrupt_entry_skipped_with_recompile_parity(self, cache_dir):
        """A bit-rotted committed payload fails its manifest checksum,
        degrades to a recompile (never a crash), and the recompiled run
        reaches exact numerical parity with the cold run."""
        samples = _samples()
        _pin_shuffle()
        o1, m1 = _trainer(samples)
        o1.optimize()
        key = next(n[:-4] for n in os.listdir(cache_dir)
                   if n.endswith(".bin"))
        p = os.path.join(cache_dir, f"{key}.bin")
        blob = bytearray(open(p, "rb").read())
        blob[len(blob) // 2] ^= 0x10
        with open(p, "wb") as f:
            f.write(bytes(blob))
        errors_before = _counter("Compile/cache_errors")
        _pin_shuffle()
        o2, m2 = _trainer(samples)
        o2.optimize()
        cached = _cached_of(o2)
        assert cached.cache_hits == 0 and cached.compiles == 1
        assert _counter("Compile/cache_errors") == errors_before + 1
        assert np.array_equal(_flat(m1.params), _flat(m2.params))

    def test_torn_and_uncommitted_entries_skipped(self, cache_dir):
        """Newest-first degradation over damaged entries: a truncated
        payload and a commit-less (torn-write) entry are both misses."""
        samples = _samples()
        _pin_shuffle()
        o1, _ = _trainer(samples)
        o1.optimize()
        key = next(n[:-4] for n in os.listdir(cache_dir)
                   if n.endswith(".bin"))
        # truncated payload (the realistic torn write: rename committed
        # a short object)
        p = os.path.join(cache_dir, f"{key}.bin")
        blob = open(p, "rb").read()
        with open(p, "wb") as f:
            f.write(blob[:len(blob) // 2])
        o2, _ = _trainer(samples)
        o2.optimize()
        assert _cached_of(o2).cache_hits == 0
        # uncommitted: the commit marker never landed
        os.unlink(os.path.join(cache_dir, f"{key}.commit"))
        o3, _ = _trainer(samples)
        o3.optimize()
        c3 = _cached_of(o3)
        assert c3.cache_hits == 0 and c3.compiles == 1

    def test_version_skew_is_miss_not_crash(self, cache_dir):
        samples = _samples()
        o1, _ = _trainer(samples)
        o1.optimize()
        key = next(n[:-5] for n in os.listdir(cache_dir)
                   if n.endswith(".json"))
        man_p = os.path.join(cache_dir, f"{key}.json")
        with open(man_p) as f:
            manifest = json.load(f)
        manifest["fingerprint"]["jax"] = "999.0.0"
        mbytes = json.dumps(manifest, sort_keys=True).encode()
        with open(man_p, "wb") as f:
            f.write(mbytes)
        from bigdl_tpu.visualization.crc32c import crc32c
        with open(os.path.join(cache_dir, f"{key}.commit"), "wb") as f:
            f.write(f"{crc32c(mbytes):08x}\n".encode())
        o2, _ = _trainer(samples)
        o2.optimize()
        c2 = _cached_of(o2)
        assert c2.cache_hits == 0 and c2.compiles == 1

    def test_newer_schema_is_miss_not_crash(self, cache_dir):
        cc = CompileCache(cache_dir)
        cc.store("deadbeef", b"payload", "x", "sig", None,
                 backend_fingerprint())
        man_p = os.path.join(cache_dir, "deadbeef.json")
        with open(man_p) as f:
            manifest = json.load(f)
        manifest["version"] = 99
        mbytes = json.dumps(manifest, sort_keys=True).encode()
        with open(man_p, "wb") as f:
            f.write(mbytes)
        from bigdl_tpu.visualization.crc32c import crc32c
        with open(os.path.join(cache_dir, "deadbeef.commit"), "wb") as f:
            f.write(f"{crc32c(mbytes):08x}\n".encode())
        assert cc.load("deadbeef", None, backend_fingerprint()) is None

    def test_topology_mismatch_is_miss(self, cache_dir):
        cc = CompileCache(cache_dir)
        topo = {"device_count": 8, "axes": {"data": 8}, "step": "shard_map",
                "slot_axis": "data"}
        fp = backend_fingerprint()
        cc.store("cafe01", b"payload", "x", "sig", topo, fp)
        assert cc.load("cafe01", topo, fp) == b"payload"
        other = dict(topo, device_count=4, axes={"data": 4})
        assert cc.load("cafe01", other, fp) is None

    def test_concurrent_writer_lock(self, cache_dir):
        """A held (fresh) lock makes the second writer back off and SKIP
        the store — no corruption, no exception; a stale lock from a
        hard-killed writer is stolen."""
        os.makedirs(cache_dir, exist_ok=True)
        lock = os.path.join(cache_dir, CompileCache.LOCK_NAME)
        with open(lock, "w") as f:
            f.write("held\n")
        cc = CompileCache(cache_dir)
        cc.lock_timeout = 0.05
        fp = backend_fingerprint()
        assert cc.store("aa01", b"data", "x", "sig", None, fp) is False
        assert not os.path.exists(os.path.join(cache_dir, "aa01.bin"))
        assert os.path.exists(lock), "a held lock must not be removed"
        # stale lock: pretend the holder died long ago
        old = os.path.getmtime(lock) - 10_000
        os.utime(lock, (old, old))
        cc.lock_stale = 600.0
        assert cc.store("aa01", b"data", "x", "sig", None, fp) is True
        assert cc.load("aa01", None, fp) == b"data"
        assert not os.path.exists(lock), "the writer releases the lock"

    def test_gc_keep_last_commit_first(self, cache_dir, monkeypatch):
        """Retention keeps the newest ``keepLast`` entries; eviction
        removes the commit marker FIRST (an interrupted GC leaves an
        ignored uncommitted entry, never a committed half-entry)."""
        cc = CompileCache(cache_dir, keep_last=2)
        fp = backend_fingerprint()
        now = [1000.0]

        def tick():
            now[0] += 10
            return now[0]

        monkeypatch.setattr(compile_cache.time, "time", tick)
        for i in range(4):
            cc.store(f"e{i:02d}", b"x" * 8, "x", "sig", None, fp)
        left = {n for n in os.listdir(cache_dir) if n.endswith(".commit")}
        assert left == {"e02.commit", "e03.commit"}
        # eviction order: commit before payload before manifest
        removed = []
        real_unlink = os.unlink
        monkeypatch.setattr(
            os, "unlink",
            lambda p: (removed.append(os.path.basename(p)),
                       real_unlink(p))[1])
        cc.keep_last = 1
        cc.gc()
        assert removed[0] == "e02.commit"
        assert removed.index("e02.commit") < removed.index("e02.bin") < \
            removed.index("e02.json")


# ---------------------------------------------------------------------------
# chaos: fault-injection proofs
# ---------------------------------------------------------------------------

class TestChaos:
    def test_corrupt_compile_cache_at_falls_back(self, cache_dir):
        """``bigdl.chaos.corruptCompileCacheAt=1`` bit-flips the first
        entry written (post-checksum): the cold run is untouched, the
        warm run detects the corruption, recompiles, and reaches exact
        weight parity."""
        samples = _samples()
        config.set_property("bigdl.chaos.corruptCompileCacheAt", 1)
        chaos.install()
        try:
            _pin_shuffle()
            o1, m1 = _trainer(samples)
            o1.optimize()
        finally:
            chaos.uninstall()
            config.clear_property("bigdl.chaos.corruptCompileCacheAt")
        _pin_shuffle()
        o2, m2 = _trainer(samples)
        o2.optimize()
        c2 = _cached_of(o2)
        assert c2.cache_hits == 0 and c2.compiles == 1, \
            "the corrupted entry must degrade to a recompile"
        assert np.array_equal(_flat(m1.params), _flat(m2.params))

    def test_hang_compile_watchdog_aborts_with_diagnosis(self):
        """``bigdl.chaos.hangCompileAt`` wedges the compile; the
        watchdog detects it within ``bigdl.compile.timeoutSec`` and the
        raised ``CompileTimeoutError`` names the signature+topology."""
        config.set_property("bigdl.compile.timeoutSec", 0.2)
        config.set_property("bigdl.chaos.hangCompileAt", "1:1.2")
        chaos.install()
        fired_before = _counter("Compile/watchdog_fired")
        step = tracked_jit(lambda x: x * 2, label="wedge",
                           topology={"device_count": 1, "step": "local"})
        t0 = telemetry.clock_ns()
        try:
            with pytest.raises(CompileTimeoutError) as ei:
                step(np.ones((4,), np.float32))
        finally:
            chaos.uninstall()
            config.clear_property("bigdl.compile.timeoutSec")
            config.clear_property("bigdl.chaos.hangCompileAt")
        wall_s = (telemetry.clock_ns() - t0) / 1e9
        assert "wedge" in str(ei.value) and "topology" in str(ei.value)
        assert ei.value.diagnosis["label"] == "wedge"
        assert _counter("Compile/watchdog_fired") == fired_before + 1
        # detected at ~timeout; the abort lands within one 20 ms chaos
        # sleep slice of the injection — all well inside the wedge span
        assert wall_s < 1.1, \
            f"abort took {wall_s:.2f}s — watchdog did not cut the wedge"

    def test_hung_compile_retried_like_divergence(self, cache_dir):
        """End to end: a wedged compile inside optimize() aborts via
        CompileTimeoutError and the retry loop RETRIES it (chaos wedges
        once), so training completes — classified like divergence
        (restore/retry), unlike Preempted (leave)."""
        samples = _samples()
        # the timeout must clear a REAL compile of this step (~0.3 s on
        # a loaded 1-core host) while still cutting the 6 s wedge fast
        config.set_property("bigdl.compile.timeoutSec", 2.0)
        config.set_property("bigdl.chaos.hangCompileAt", "1:6.0")
        config.set_property("bigdl.failure.retryTimeInterval", 0.0)
        chaos.install()
        try:
            _pin_shuffle()
            o, m = _trainer(samples)
            o.optimize()
        finally:
            chaos.uninstall()
            for k in ("bigdl.compile.timeoutSec",
                      "bigdl.chaos.hangCompileAt",
                      "bigdl.failure.retryTimeInterval"):
                config.clear_property(k)
        assert o.optim_method.state.get("evalCounter", 0) >= 6, \
            "training must complete after the compile-timeout retry"


# ---------------------------------------------------------------------------
# shape buckets
# ---------------------------------------------------------------------------

class TestBuckets:
    def test_bucket_size_rounding(self):
        buckets = [8, 16, 32]
        assert bucket_size(1, buckets) == 8
        assert bucket_size(8, buckets) == 8
        assert bucket_size(9, buckets) == 16
        assert bucket_size(32, buckets) == 32
        assert bucket_size(33, buckets) == 64   # multiples of the largest
        assert bucket_size(65, buckets) == 96

    def test_pad_and_slice_roundtrip(self):
        x = np.arange(12, dtype=np.float32).reshape(3, 4)
        padded = pad_batch({"a": x}, 3, 8)
        assert padded["a"].shape == (8, 4)
        np.testing.assert_array_equal(padded["a"][:3], x)
        np.testing.assert_array_equal(padded["a"][3:],
                                      np.repeat(x[-1:], 5, axis=0))
        back = slice_rows(padded, 3)
        np.testing.assert_array_equal(back["a"], x)

    def _eval_model(self):
        m = (nn.Sequential().add(nn.Linear(8, 16)).add(nn.ReLU())
             .add(nn.Linear(16, 3)).add(nn.LogSoftMax()))
        m.reset(jax.random.PRNGKey(3))
        m._ensure_init()
        return m

    def test_ragged_validation_zero_retraces_strict(self):
        """THE retrace gate (acceptance criterion): ragged validation
        batch sizes under strict sentinel + buckets complete with zero
        post-warmup retraces AND identical metric results to the
        unbucketed run."""
        from bigdl_tpu.optim.evaluator import evaluate_dataset
        from bigdl_tpu.optim.validation_method import Top1Accuracy, Loss
        samples = _samples(n=57, seed=5)   # 57 = ragged under any batch
        m = self._eval_model()
        methods = [Top1Accuracy(), Loss(nn.ClassNLLCriterion())]

        def run(batch):
            from bigdl_tpu.dataset.transformer import SampleToMiniBatch
            batches = list(SampleToMiniBatch(batch)(iter(samples)))
            return evaluate_dataset(m, batches, methods)

        # baseline, no buckets (fresh eval cache)
        ref = [(meth.name, r.final_result()) for meth, r in run(16)]
        m._eval_jit = {}
        config.set_property("bigdl.compile.buckets", "4,8,16")
        try:
            # ragged sizes: 16,16,16,9 -> buckets 16 and 16(pad);
            # then batch 10 -> bucket 16 again, 7 -> 16/8 ...
            got = [(meth.name, r.final_result()) for meth, r in run(16)]
            got2 = [(meth.name, r.final_result()) for meth, r in run(10)]
            fn = m._eval_jit[id(None)]
            sentinel = fn.sentinel
            assert sentinel.retraces == 0, sentinel.last_diff
            cached = fn.__wrapped__
            # every signature the ragged runs produced was pre-compiled
            assert len(cached._mem) >= 3   # 16 + bucket variants 4, 8
        finally:
            config.clear_property("bigdl.compile.buckets")
            m._eval_jit = {}
        for (n1, a), (n2, b) in zip(ref, got):
            assert n1 == n2 and abs(a - b) < 1e-6, \
                "bucketed metrics must match the unbucketed run"
        for (n1, a), (n2, b) in zip(ref, got2):
            assert n1 == n2 and abs(a - b) < 1e-6

    def test_unbucketed_signature_is_a_retrace(self):
        """The gate has teeth: a shape that escapes the bucket plan (a
        direct eval call with an un-bucketed batch size) is a
        post-warmup retrace — strict raises."""
        from bigdl_tpu.optim.evaluator import _eval_forward
        from bigdl_tpu.analysis.retrace import RetraceError
        from bigdl_tpu.engine import to_device
        m = self._eval_model()
        config.set_property("bigdl.compile.buckets", "4,8")
        try:
            fwd = _eval_forward(m)
            fwd(to_device(np.zeros((4, 8), np.float32)))
            fwd(to_device(np.zeros((8, 8), np.float32)))   # bucket: fine
            with pytest.raises(RetraceError):
                fwd(to_device(np.zeros((5, 8), np.float32)))
        finally:
            config.clear_property("bigdl.compile.buckets")
            m._eval_jit = {}

    def test_sharded_eval_bucket_variants(self):
        """Mesh-sharded eval + buckets: variants divisible by the data
        axis precompile from abstract specs and SERVE later concrete
        ragged batches; non-divisible variants are skipped (those
        batches run the local fallback) — never fatal, zero retraces,
        metrics identical to the unbucketed sharded run."""
        from jax.sharding import Mesh
        from bigdl_tpu.optim.evaluator import evaluate_dataset
        from bigdl_tpu.optim.validation_method import Top1Accuracy
        from bigdl_tpu.dataset.transformer import SampleToMiniBatch
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual mesh")
        mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))
        m = self._eval_model()
        samples = _samples(n=57, seed=5)

        def run(batch):
            batches = list(SampleToMiniBatch(batch)(iter(samples)))
            return [(meth.name, r.final_result()) for meth, r in
                    evaluate_dataset(m, batches, [Top1Accuracy()],
                                     mesh=mesh)]

        ref = run(16)
        m._eval_jit = {}
        config.set_property("bigdl.compile.buckets", "4,8,16")
        try:
            got = run(16)           # 16,16,16,9->16: one sharded sig
            got2 = run(13)          # 13->16 hit; 5->8: the spec variant
            fn = m._eval_jit[id(mesh)]
            assert fn.sentinel.retraces == 0, fn.sentinel.last_diff
            # bucket 8 precompiled from specs; bucket 4 (not divisible
            # by the 8-way axis) skipped without killing the eval
            assert len(fn.__wrapped__._mem) == 2
        finally:
            config.clear_property("bigdl.compile.buckets")
            m._eval_jit = {}
        assert got == ref, "bucketed sharded metrics must match unbucketed"

    def test_oversize_batches_are_in_plan(self):
        """Batch sizes beyond the largest bucket round to its multiples
        — sizes the precompiler cannot enumerate ahead.  Two distinct
        oversize predict sizes of the SAME signature family must compile
        as in-plan warmup, not raise as retraces (they followed the
        bucket plan); a call differing in anything but the batch dim is
        a new family and still trips the strict gate."""
        from bigdl_tpu.optim.predictor import Predictor
        from bigdl_tpu.analysis.retrace import RetraceError
        from bigdl_tpu.engine import to_device
        m = self._eval_model()
        samples = [Sample(np.random.RandomState(i).normal(
            size=(8,)).astype(np.float32), np.float32(1))
            for i in range(48)]
        config.set_property("bigdl.compile.buckets", "4,8")
        try:
            a = Predictor(m).predict(samples, batch_size=16)  # 16 = 2x8
            b = Predictor(m).predict(samples, batch_size=24)  # 24 = 3x8
            fn = m._eval_jit[id(None)]
            assert fn.sentinel.retraces == 0, fn.sentinel.last_diff
            np.testing.assert_array_equal(a, b)
            # the gate keeps its teeth: same batch dim, different
            # feature width = a different family = a strict raise
            with pytest.raises(RetraceError):
                fn(m.params, m.state, to_device(
                    np.zeros((8, 9), np.float32)))
        finally:
            config.clear_property("bigdl.compile.buckets")
            m._eval_jit = {}

    def test_predictor_bucketed_parity(self):
        """Ragged predict batches under buckets: outputs match the
        unbucketed run, and execution stays inside the precompiled
        signature set.

        Parity is to float32 rounding, not bitwise: the ragged 3-row
        batch runs a 3-row program unbucketed and the 4-row program
        bucketed, and XLA does not promise that two separately compiled
        batch shapes round a row identically (jaxlib 0.9.0's CPU
        backend: a 2-row and a 24-row compile of the same
        Linear-Tanh-Linear-LogSoftMax differ by 1 ulp in the same
        row)."""
        from bigdl_tpu.optim.predictor import Predictor
        m = self._eval_model()
        samples = [Sample(np.random.RandomState(i).normal(
            size=(8,)).astype(np.float32), np.float32(1))
            for i in range(11)]                       # 8 + ragged 3
        ref = Predictor(m).predict(samples, batch_size=8)
        m._eval_jit = {}
        config.set_property("bigdl.compile.buckets", "4,8")
        try:
            got = Predictor(m).predict(samples, batch_size=8)
            fn = m._eval_jit[id(None)]
            assert fn.sentinel.retraces == 0
        finally:
            config.clear_property("bigdl.compile.buckets")
            m._eval_jit = {}
        np.testing.assert_allclose(ref, got, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# AOT warmup phase
# ---------------------------------------------------------------------------

class TestWarmup:
    def test_warmup_gauge_and_prestep_compile(self, cache_dir):
        """The driver's warmup phase compiles before step 1 and charts
        ``Compile/warmup_ms``; the step object is warm by the time the
        first iteration dispatches."""
        samples = _samples()
        o, _ = _trainer(samples, iterations=3)
        o.optimize()
        snap = telemetry.REGISTRY.snapshot()["gauges"]
        assert snap.get("Compile/warmup_ms", 0) > 0
        assert _cached_of(o).warm

    def test_second_optimize_reuses_in_memory(self, cache_dir):
        samples = _samples()
        o, _ = _trainer(samples, iterations=3)
        o.optimize()
        cached = _cached_of(o)
        o.set_end_when(optim.max_iteration(6))
        o.optimize()
        assert cached.compiles == 1, \
            "a second optimize() must reuse the in-memory executable"


# ---------------------------------------------------------------------------
# lint: the untracked-jit rule
# ---------------------------------------------------------------------------

class TestUntrackedJitLint:
    def _lint(self, tmp_path, source, name="pkg/mod.py"):
        from bigdl_tpu.analysis.lint import lint_paths
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        (p.parent / "__init__.py").write_text("")
        p.write_text(source)
        return [f.rule for f in lint_paths([str(p)])]

    def test_flags_jit_lower_compile(self, tmp_path):
        rules = self._lint(tmp_path, (
            "import jax\n"
            "f = jax.jit(lambda x: x)\n"
            "low = f.lower(x)\n"
            "exe = low.compile()\n"
            "@jax.jit\n"
            "def g(x):\n"
            "    return x\n"))
        assert rules.count("untracked-jit") == 4

    def test_ignores_str_lower_and_re_compile(self, tmp_path):
        rules = self._lint(tmp_path, (
            "import re\n"
            "s = 'ABC'.lower()\n"
            "rx = re.compile('a+')\n"))
        assert "untracked-jit" not in rules

    def test_inline_allow(self, tmp_path):
        rules = self._lint(tmp_path, (
            "import jax\n"
            "f = jax.jit(lambda x: x)  # lint: allow(untracked-jit)\n"))
        assert "untracked-jit" not in rules

    def test_wrapper_file_exempt(self, tmp_path):
        rules = self._lint(tmp_path, (
            "import jax\n"
            "f = jax.jit(lambda x: x)\n"
            "e = f.lower(1).compile()\n"), name="utils/compile_cache.py")
        assert "untracked-jit" not in rules


# ---------------------------------------------------------------------------
# concurrent readers of one cache dir (ISSUE 17 satellite)
# ---------------------------------------------------------------------------

class TestConcurrentReaders:
    def test_two_warm_loaders_while_a_third_writes(self, cache_dir):
        """The fleet hot-swap access pattern: replicas of a candidate
        warm-load the SAME committed entry concurrently while another
        engine's compile stores a brand-new one into the same directory
        — readers never observe a torn entry, never take a fresh
        compile, and serve bit-identical results."""
        import threading

        from bigdl_tpu.serving import ServingEngine

        config.set_property("bigdl.compile.buckets", "2,4")
        try:
            def eval_model(seed=7):
                m = (nn.Sequential().add(nn.Linear(4, 16)).add(nn.Tanh())
                     .add(nn.Linear(16, 3)))
                m.reset(jax.random.PRNGKey(seed))
                return m

            def step_of(model):
                fn = model._eval_jit[id(None)]
                return getattr(fn, "__wrapped__", fn)

            row = np.arange(4, dtype=np.float32)
            seeder = ServingEngine(eval_model())
            seeder.warmup(np.zeros((4,), np.float32))
            assert step_of(seeder.model).compiles >= 1
            want = seeder.submit(row).result(timeout=10.0)
            seeder.stop()

            barrier = threading.Barrier(3)
            results, errors = {}, []

            def reader(tag):
                try:
                    model = eval_model()
                    barrier.wait(timeout=10)
                    eng = ServingEngine(model)
                    eng.warmup(np.zeros((4,), np.float32))
                    results[tag] = (np.asarray(
                        eng.submit(row).result(timeout=10.0)),
                        step_of(model))
                    eng.stop()
                except Exception as e:       # surfaced after join
                    errors.append((tag, e))

            def writer():
                try:
                    cc = CompileCache(cache_dir)
                    fp = backend_fingerprint()
                    barrier.wait(timeout=10)
                    for i in range(20):
                        assert cc.store(f"feed{i:02d}", b"x" * 256,
                                        "probe", f"sig{i}", None, fp)
                except Exception as e:
                    errors.append(("writer", e))

            threads = [threading.Thread(target=reader, args=("r1",)),
                       threading.Thread(target=reader, args=("r2",)),
                       threading.Thread(target=writer)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert errors == []
            for tag in ("r1", "r2"):
                out, step = results[tag]
                assert step.compiles == 0, \
                    f"{tag} recompiled under a concurrent writer"
                assert step.cache_hits >= 1
                np.testing.assert_array_equal(out, want)
            # the writer's entries all committed despite the read storm
            cc = CompileCache(cache_dir)
            fp = backend_fingerprint()
            for i in range(20):
                assert cc.load(f"feed{i:02d}", None, fp) == b"x" * 256
        finally:
            config.clear_property("bigdl.compile.buckets")
