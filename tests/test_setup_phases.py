"""Where set-up time goes, as the program records it.

The claims under test: every executable a ``CachedStep`` makes passes
through named phases, each timed once, into its ``timings`` entry and the
process-wide histogram ``Compile/phase_ms{phase=...}``, and while spans
record as ``compile/<phase>`` spans inside ``compile/<label>``; splitting
the trace from the lowering leaves the cache key what it was; an
executable that a persistent cache answers (the program's store, or XLA's
compilation cache inside ``compile()``) is a ``load``, not a ``compile``;
the LM engine's construction and warm-up, and ``optimize()`` up to step
1's dispatch, time themselves into ``Setup/program_ms{entry=...}``.  No
assertion here reads a duration against a wall-clock threshold: counts,
containment on the one monotonic clock, and sums against sums.
"""

import collections
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu import telemetry
from bigdl_tpu.dataset import LocalDataSet, SampleToMiniBatch
from bigdl_tpu.dataset.datasets import synthetic_separable
from bigdl_tpu.models.transformer import transformer_lm
from bigdl_tpu.serving import LMServingEngine
from bigdl_tpu.utils import config
from bigdl_tpu.utils.compile_cache import (PHASES, CompileCache,
                                           _signature_hash,
                                           backend_fingerprint, tracked_jit)

X = np.arange(16, dtype=np.float32).reshape(4, 4)


@pytest.fixture(autouse=True)
def _fresh_histograms():
    for prefix in ("Compile/phase_ms", "Compile/xla_cache_read_ms",
                   "Setup/"):
        telemetry.REGISTRY.drop_prefix(prefix)
    yield


@pytest.fixture
def store(tmp_path):
    d = str(tmp_path / "ccache")
    config.set_property("bigdl.compile.cacheDir", d)
    yield d
    config.clear_property("bigdl.compile.cacheDir")


def _toy():
    def toy(a):
        return jnp.tanh(a @ a.T) * 2.0
    return toy


def _phase(name):
    return telemetry.histogram("Compile/phase_ms", labels={"phase": name})


def _counts():
    return {p: _phase(p).count for p in PHASES}


def _sums():
    return sum(_phase(p).sum for p in PHASES)


def _setup(entry):
    return telemetry.histogram("Setup/program_ms", labels={"entry": entry})


def _hit_share():
    loads, compiles = _phase("load").count, _phase("compile").count
    return loads / (loads + compiles)


def test_cold_then_warm_store_goes_through_every_phase(store):
    """A cold step compiles and stores; a fresh step over the same store
    reads and loads.  Each phase shows in the entry and is counted once
    per executable that went through it; the trace is the jaxpr plus the
    lowering; and the gauges that held the last executable's value are
    gone."""
    cold = tracked_jit(_toy(), label="toy")
    cold(X)
    after_cold = _counts()
    assert after_cold == {"jaxpr": 1, "lower": 1, "key": 1, "read": 0,
                          "load": 0, "compile": 1, "audit": 1, "store": 1}
    assert _hit_share() == 0.0
    warm = tracked_jit(_toy(), label="toy")
    warm(X)
    delta = {p: n - after_cold[p] for p, n in _counts().items()}
    assert delta == {"jaxpr": 1, "lower": 1, "key": 1, "read": 1,
                     "load": 1, "compile": 0, "audit": 1, "store": 0}
    assert _hit_share() == 0.5
    c, = cold.timings
    w, = warm.timings
    assert (c["source"], w["source"]) == ("compile", "cache")
    for entry in (c, w):
        for key in ("jaxpr_ms", "lower_ms", "key_ms", "read_ms",
                    "store_ms"):
            assert entry[key] >= 0.0
    assert c["trace_ms"] == pytest.approx(c["jaxpr_ms"] + c["lower_ms"],
                                          abs=1e-9)
    assert c["read_ms"] == 0.0 and c["xla_cache"] is False
    assert w["store_ms"] == 0.0 and "compile_ms" not in w
    assert "trace_ms" not in w          # step_trace_s keeps its meaning
    assert w["load_ms"] > 0.0
    gauges = telemetry.REGISTRY.snapshot()["gauges"]
    for gone in ("Compile/trace_ms", "Compile/compile_ms",
                 "Compile/load_ms"):
        assert gone not in gauges


def test_phase_spans_lie_inside_the_step_span(store):
    """Armed (the suite's conftest arms the tracer), each executable
    leaves one ``compile/<label>`` span holding its phases' spans, on the
    same clock reads the histogram was fed from."""
    tracked_jit(_toy(), label="toy")(X)
    tracked_jit(_toy(), label="toy")(X)
    spans = [e for e in telemetry.events() if e["name"].startswith(
        "compile/")]
    outer = [e for e in spans if e["name"] == "compile/toy"]
    assert len(outer) == 2
    phases = [e for e in spans if e["name"] != "compile/toy"]
    by_name = collections.Counter(e["name"] for e in phases)
    assert by_name == {f"compile/{p}": _phase(p).count for p in PHASES
                       if _phase(p).count}
    for e in phases:
        assert any(o["t0_ns"] <= e["t0_ns"] <= e["t1_ns"] <= o["t1_ns"]
                   for o in outer), e
    assert sum(e["t1_ns"] - e["t0_ns"] for e in phases) / 1e6 == \
        pytest.approx(_sums(), rel=1e-9)


def test_cache_key_is_the_digest_of_the_lowered_text(store):
    """The key is still the digest of what ``jax.jit(f).lower(*args)``
    prints, so entries written before the trace was split from the
    lowering still hit."""
    step = tracked_jit(_toy(), label="toy")
    step(X)
    args = (jnp.asarray(X),)
    digest = hashlib.sha256(
        jax.jit(_toy()).lower(*args).as_text().encode("utf-8")).hexdigest()
    want = CompileCache.entry_key("toy", _signature_hash(args), digest,
                                  None, backend_fingerprint())
    assert f"{want}.commit" in os.listdir(store)


@pytest.fixture
def xla_cache(tmp_path):
    """XLA's persistent compilation cache on, in a directory of the
    test's own, for every program however quick to compile; the suite's
    state restored after."""
    from jax._src import compilation_cache
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "xla"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_an_answer_from_xlas_compilation_cache_is_a_load(xla_cache):
    """With no executable store, a second process's ``compile()`` is
    answered by XLA's persistent cache: that executable was loaded, not
    compiled, and XLA's own retrieval time is recorded beside it."""
    first = tracked_jit(_toy(), label="toy")
    first(X)
    second = tracked_jit(_toy(), label="toy")
    second(X)
    a, = first.timings
    b, = second.timings
    assert (a["xla_cache"], b["xla_cache"]) == (False, True)
    assert a["source"] == b["source"] == "compile"
    assert _phase("compile").count == 1 and _phase("load").count == 1
    assert _phase("key").count == 2 and _phase("store").count == 0
    read = telemetry.histogram("Compile/xla_cache_read_ms")
    assert read.count == 1 and read.sum <= _phase("load").sum


VOCAB = 32


def _engine():
    m = transformer_lm(VOCAB, d_model=16, n_head=2, n_layers=2, max_len=64)
    m.reset(jax.random.PRNGKey(3))
    return LMServingEngine(m, max_batch=4, max_context=32, block_size=4,
                           deadline_ms=30000.0)


def test_engine_construction_and_warmup_time_themselves():
    """One observation each; the warm-up holds every phase of every
    executable it made, on one monotonic clock: its span encloses their
    spans and its sum is at least theirs."""
    eng = _engine()
    assert _setup("lm_construct").count == 1
    assert _setup("lm_warmup").count == 0
    before = _sums()
    eng.warmup()
    made = sum(len(v) for v in eng.timings.values())
    assert made >= 3 and _phase("jaxpr").count >= made
    assert _setup("lm_construct").count == 1
    assert _setup("lm_warmup").count == 1
    assert _setup("lm_warmup").sum >= _sums() - before
    events = telemetry.events()
    warm, = [e for e in events if e["name"] == "lm/warmup"]
    built, = [e for e in events if e["name"] == "lm/construct"]
    assert built["t1_ns"] <= warm["t0_ns"]
    inner = [e for e in events if e["name"].startswith("compile/")
             and e["t0_ns"] >= built["t1_ns"]]
    assert inner
    for e in inner:
        assert warm["t0_ns"] <= e["t0_ns"] <= e["t1_ns"] <= warm["t1_ns"]


def test_optimize_times_itself_to_step_one_once():
    """``optimize()`` from its entry to step 1's dispatch: one observation
    for the call, a ``driver/setup`` span on the driver's lane that holds
    the compile warm-up and ends before the first device step."""
    samples = synthetic_separable(64, 8, n_classes=2, seed=2)
    ds = LocalDataSet(samples).transform(SampleToMiniBatch(16))
    model = nn.Sequential().add(nn.Linear(8, 4)).add(nn.LogSoftMax())
    model.reset(jax.random.PRNGKey(1))
    opt = optim.Optimizer.create(model, ds, nn.ClassNLLCriterion())
    opt.set_optim_method(optim.SGD(learning_rate=0.1))
    opt.set_end_when(optim.max_iteration(4))
    opt.optimize()
    assert _setup("optimize").count == 1
    assert _setup("optimize").sum >= _sums()
    lane = [e for e in telemetry.events() if e["thread"] == "driver"]
    setup, = [e for e in lane if e["name"] == "driver/setup"]
    warmup, = [e for e in lane if e["name"] == "driver/compile_warmup"]
    first = min(e["t0_ns"] for e in lane
                if e["name"] == "driver/device_step")
    assert setup["t0_ns"] <= warmup["t0_ns"] <= warmup["t1_ns"] \
        <= setup["t1_ns"] <= first
    assert opt._setup_t0 is None
