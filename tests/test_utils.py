"""Tests for bigdl_tpu.utils (reference test analog: utils/ specs)."""

import os

import numpy as np
import pytest

from bigdl_tpu.utils import (DirectedGraph, Edge, Node, RandomGenerator, T,
                             Table, file_io, kth_largest)


class TestTable:
    def test_t_constructor(self):
        t = T(10, 20, x=3)
        assert t[1] == 10 and t[2] == 20 and t["x"] == 3
        assert t.length() == 2

    def test_insert_remove(self):
        t = T("a", "b", "c")
        t.insert(2, "z")
        assert t.to_seq() == ["a", "z", "b", "c"]
        assert t.remove(2) == "z"
        assert t.to_seq() == ["a", "b", "c"]
        t.insert("d")
        assert t.length() == 4

    def test_pytree(self):
        import jax
        t = T(np.ones(3), np.zeros(2))
        doubled = jax.tree_util.tree_map(lambda x: x * 2, t)
        assert isinstance(doubled, Table)
        np.testing.assert_allclose(doubled[1], 2 * np.ones(3))

    def test_get_or_update(self):
        t = Table()
        assert t.get_or_update("k", lambda: 5) == 5
        assert t.get_or_update("k", lambda: 99) == 5


class TestFileIO:
    def test_save_load_roundtrip(self, tmp_path):
        obj = {"a": np.arange(5), "b": "text"}
        p = str(tmp_path / "obj.bin")
        file_io.save(obj, p)
        loaded = file_io.load(p)
        np.testing.assert_array_equal(loaded["a"], obj["a"])
        assert loaded["b"] == "text"

    def test_no_overwrite(self, tmp_path):
        p = str(tmp_path / "f.bin")
        file_io.save(1, p)
        with pytest.raises(FileExistsError):
            file_io.save(2, p, overwrite=False)

    def test_remote_scheme_dispatches_to_fsspec(self):
        # schemes route through fsspec, which names the missing client
        # (s3fs / a JVM for HDFS) when one is not installed in this image
        with pytest.raises(Exception, match="s3fs|S3"):
            file_io.save(1, "s3://bucket/path")


class TestRandomGenerator:
    def test_seed_reproducible(self):
        a = RandomGenerator(42)
        b = RandomGenerator(42)
        assert [a.uniform() for _ in range(5)] == [b.uniform() for _ in range(5)]

    def test_thread_local_singleton(self):
        assert RandomGenerator.RNG() is RandomGenerator.RNG()

    def test_permutation(self):
        p = RandomGenerator(1).permutation(10)
        assert sorted(p.tolist()) == list(range(10))


class TestDirectedGraph:
    def _diamond(self):
        a, b, c, d = Node("a"), Node("b"), Node("c"), Node("d")
        a.add(b)
        a.add(c)
        b.add(d)
        c.add(d)
        return a, b, c, d

    def test_topsort(self):
        a, b, c, d = self._diamond()
        order = [n.element for n in a.graph().topology_sort()]
        assert order[0] == "a" and order[-1] == "d"
        assert set(order) == {"a", "b", "c", "d"}

    def test_bfs_dfs(self):
        a, *_ = self._diamond()
        assert len(list(a.graph().bfs())) == 4
        assert len(list(a.graph().dfs())) == 4

    def test_reverse_graph(self):
        a, b, c, d = self._diamond()
        rev = [n.element for n in d.graph(reverse=True).topology_sort()]
        assert rev[0] == "d" and rev[-1] == "a"

    def test_cycle_detection(self):
        a, b = Node("a"), Node("b")
        a.add(b)
        b.add(a)
        with pytest.raises(ValueError):
            a.graph().topology_sort()

    def test_clone(self):
        a, *_ = self._diamond()
        g2 = a.graph().clone_graph()
        assert g2.size() == 4
        assert g2.source is not a


def test_kth_largest():
    arr = [5, 1, 9, 3, 7]
    assert kth_largest(arr, 1) == 9
    assert kth_largest(arr, 3) == 5
    assert kth_largest(arr, 5) == 1


def test_init_distributed_single_process_bringup():
    """Engine.init_distributed joins the jax distributed runtime (the
    multi-host tier) — exercised single-process in a subprocess so the
    global coordination client cannot leak into this test session."""
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    code = f"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
from bigdl_tpu.engine import Engine
Engine.init_distributed("127.0.0.1:{port}", 1, 0)
Engine.init_distributed("127.0.0.1:{port}", 1, 0)   # idempotent no-op
import jax
assert jax.process_count() == 1
assert jax.process_index() == 0
print("BRINGUP_OK")
"""
    # strip inherited accelerator vars: TPU_*/PJRT_* would trigger
    # jax's TPU cluster auto-detection and pre-init the backend
    def _keep(k):
        return not (k in ("JAX_PLATFORMS", "XLA_FLAGS") or
                    k.startswith(("TPU_", "PALLAS_", "PJRT_")))
    env = {k: v for k, v in os.environ.items() if _keep(k)}
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], cwd=repo_root,
                         capture_output=True, text=True, timeout=120,
                         env=env)
    assert "BRINGUP_OK" in out.stdout, out.stderr[-2000:]


class TestDispatchPipeline:
    def test_depth_one_is_synchronous(self):
        from bigdl_tpu.engine import DispatchPipeline
        drained = []
        p = DispatchPipeline(lambda item, nxt: drained.append(item[0]),
                             depth=1)
        p.push("a")
        assert drained == ["a"], "depth=1 must drain at every push"
        p.push("b")
        assert drained == ["a", "b"]

    def test_bounded_in_flight_and_fifo(self):
        from bigdl_tpu.engine import DispatchPipeline
        drained = []
        p = DispatchPipeline(lambda item, nxt: drained.append(
            (item[0], None if nxt is None else nxt[0])), depth=3)
        for v in "abcde":
            p.push(v)
        # depth 3 keeps 2 in flight: a/b/c drained, with next-item peeks
        assert [d[0] for d in drained] == ["a", "b", "c"]
        assert drained[0] == ("a", "b")
        p.flush()
        assert [d[0] for d in drained] == list("abcde")
        assert drained[-1] == ("e", None)


def test_allgather_sum_exact_above_f32_integer_range(monkeypatch):
    """allgather_sum must keep integer exactness past 2^24 even though
    process_allgather downcasts float64 wires to float32 (jax_enable_x64
    off): values ride as float32 (hi, lo) pairs recombined in float64."""
    import jax

    from bigdl_tpu import engine as eng

    monkeypatch.setattr(jax, "process_count", lambda: 2)

    def fake_allgather(x):
        # emulate the real wire: per-process float32 payloads, stacked
        assert x.dtype == np.float32, "wire must already be float32-safe"
        return np.stack([x, x])

    fake_mod = type("M", (), {"process_allgather": staticmethod(
        fake_allgather)})
    import jax.experimental
    monkeypatch.setattr(jax.experimental, "multihost_utils", fake_mod,
                        raising=False)

    big = float(2 ** 25 + 1)            # not representable in float32
    out = eng.allgather_sum(np.array([big, 3.0]))
    np.testing.assert_array_equal(out, [2.0 * big, 6.0])


def test_batch_prefetcher_blocks_only_large_batches():
    """The ready-before-handoff guard is SIZE-GATED: bulk batches are
    blocked device-resident (the pipeline, not the consumer's dispatch,
    absorbs the wait for the transfer), while small batches stay async
    — blocking them would serialize the producer against every step."""
    from bigdl_tpu.engine import BatchPrefetcher

    calls = []

    class FakeLeaf:
        def __init__(self, nbytes):
            self.nbytes = nbytes

        def block_until_ready(self):
            calls.append(self.nbytes)

    small = (FakeLeaf(1024), FakeLeaf(2048), 64)
    big = (FakeLeaf(8 << 20), FakeLeaf(1024), 64)
    batches = iter([small, big])
    pf = BatchPrefetcher(lambda: next(batches), depth=0)
    pf()
    assert calls == [], "small batch must not be blocked"
    pf()
    assert sorted(calls) == [1024, 8 << 20], "large batch blocks all leaves"


class TestConfigProperties:
    def test_resolution_order_override_env_default(self, monkeypatch):
        from bigdl_tpu.utils import config
        # table default (shield from any ambient env setting)
        monkeypatch.delenv("BIGDL_FAILURE_RETRYTIMES", raising=False)
        assert config.get_int("bigdl.failure.retryTimes") == 5
        # env var wins over default (dots -> underscores, upper-cased)
        monkeypatch.setenv("BIGDL_FAILURE_RETRYTIMES", "9")
        assert config.get_int("bigdl.failure.retryTimes") == 9
        # programmatic override wins over env
        config.set_property("bigdl.failure.retryTimes", 3)
        try:
            assert config.get_int("bigdl.failure.retryTimes") == 3
        finally:
            config.clear_property("bigdl.failure.retryTimes")
        assert config.get_int("bigdl.failure.retryTimes") == 9

    def test_typed_getters_and_diagnostics(self, monkeypatch):
        from bigdl_tpu.utils import config
        monkeypatch.delenv("BIGDL_ENGINETYPE", raising=False)
        config.set_property("bigdl.summary.flushSecs", "2.5")
        try:
            assert config.get_float("bigdl.summary.flushSecs") == 2.5
        finally:
            config.clear_property("bigdl.summary.flushSecs")
        try:
            for truthy in ("1", "true", "YES", "on", True):
                config.set_property("bigdl.check.singleton", truthy)
                assert config.get_bool("bigdl.check.singleton") is True
            config.set_property("bigdl.check.singleton", "off")
            assert config.get_bool("bigdl.check.singleton") is False
        finally:
            config.clear_property("bigdl.check.singleton")
        table = config.known_properties()
        assert table["bigdl.engineType"] == "tpu"
        assert "bigdl.pipeline.depth" in table


def test_to_device_leaves_live_on_the_default_device():
    """Every leaf, whatever its size or container, lands on the default
    backend's first device (the removed dlpack import returned leaves
    of 64 KB and more committed to the CPU backend)."""
    import jax
    from bigdl_tpu.engine import to_device
    tree = {"small": np.ones((4,), np.float32),
            "batch": [np.zeros((8, 2048), np.float32),       # 64 KB
                      (np.arange(1 << 18, dtype=np.int32), 3.0)]}
    out = to_device(tree)
    leaves = jax.tree_util.tree_leaves(out)
    assert len(leaves) == 4
    for leaf in leaves:
        assert leaf.devices() == {jax.devices()[0]}
        assert not leaf.committed, "a committed leaf cannot follow a mesh"
    np.testing.assert_array_equal(out["batch"][1][0], tree["batch"][1][0])


class TestInitCompileCache:
    @pytest.fixture
    def updates(self, monkeypatch):
        """What the helper sets through ``jax.config.update``, recorded
        instead of applied."""
        import jax
        seen = []
        monkeypatch.setattr(jax.config, "update",
                            lambda key, value: seen.append((key, value)))
        return seen

    def test_environment_places_the_cache(self, monkeypatch, tmp_path,
                                          updates):
        from bigdl_tpu.engine import init_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert init_compile_cache() == str(tmp_path)
        assert updates == [], "with the variable set, nothing is set in code"

    def test_default_is_fixed_beside_the_package(self, monkeypatch, updates):
        import bigdl_tpu
        from bigdl_tpu.engine import init_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(bigdl_tpu.__file__))), ".jax_cache")
        assert init_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]
