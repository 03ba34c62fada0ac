"""A profiler capture arms the program's own spans (ISSUE 24).

The claims under test: ``telemetry.span`` records while a ``jax.profiler``
session runs, with no switch of its own, and every span recorded under it
has a twin on the host plane of the written ``.xplane.pb``; with neither
switch it is the shared null span and nothing is recorded, in the trainer
and in the LM server alike; the LM scheduler leaves one ``lm/iteration``
tree per pass whose self times account for the lane's slice; the
per-request histograms count admissions and not ``generate()``; the driver
starts its own capture with the Python tracer off; and the lowered decode,
prefill and train steps carry the named scopes, the same ones whatever was
built before.

The suite's conftest arms the tracer for every test, so each test here
disarms first.
"""

import collections
import glob
import re

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
from bigdl_tpu.optim.optimizer import _profiler_options
from bigdl_tpu.serving import LMServingEngine
from bigdl_tpu.telemetry import tracer

VOCAB = 32


@pytest.fixture(autouse=True)
def _disarmed():
    telemetry.disarm()
    telemetry.reset_tracer()
    yield
    telemetry.disarm()
    telemetry.reset_tracer()


def _host_events(log_dir):
    """``{name: [stats dict]}`` for every event of the host plane."""
    from jax.profiler import ProfileData
    path = max(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out[ev.name].append(dict(ev.stats))
    return out


def _session(log_dir):
    jax.profiler.start_trace(str(log_dir),
                             profiler_options=_profiler_options())


def _lm(seed=3, **kw):
    m = transformer_lm(VOCAB, d_model=16, n_head=2, n_layers=2, max_len=64,
                       **kw)
    m.reset(jax.random.PRNGKey(seed))
    return m


def _engine():
    eng = LMServingEngine(_lm(), max_batch=4, max_context=32, block_size=4,
                          deadline_ms=30000.0)
    eng.warmup()
    return eng


def _prompt(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, VOCAB + 1, size=n).astype(np.int32)


def _toy_optimizer(iterations=6):
    samples = synthetic_separable(64, 8, n_classes=2, seed=2)
    ds = LocalDataSet(samples).transform(SampleToMiniBatch(16))
    model = nn.Sequential().add(nn.Linear(8, 4)).add(nn.LogSoftMax())
    model.reset(jax.random.PRNGKey(1))
    opt = optim.Optimizer.create(model, ds, nn.ClassNLLCriterion())
    opt.set_optim_method(optim.SGD(learning_rate=0.1))
    opt.set_end_when(optim.max_iteration(iterations))
    return opt


# ---------------------------------------------------------------------------
# the arming rule
# ---------------------------------------------------------------------------

def test_profiler_session_arms_and_disarms_spans(tmp_path):
    with telemetry.span("t/before"):
        pass
    assert not telemetry.recording()
    _session(tmp_path)
    try:
        assert telemetry.recording() and not telemetry.tracing_enabled()
        with telemetry.span("t/inside", k=1):
            telemetry.instant("t/mark")
            telemetry.add_span("t/measured", 10, 20)
    finally:
        jax.profiler.stop_trace()
    with telemetry.span("t/after"):
        pass
    assert not telemetry.recording()
    names = [e["name"] for e in telemetry.events()]
    assert sorted(names) == ["t/inside", "t/mark", "t/measured"]


def test_span_under_a_session_has_a_twin_on_the_host_plane(tmp_path):
    _session(tmp_path)
    try:
        with telemetry.span("t/wait", bytes=123, trace_id="abc") as sp:
            sp.set(admitted=3)
        telemetry.add_span("t/ring_only", 10, 20)
    finally:
        jax.profiler.stop_trace()
    ring, = [e for e in telemetry.events() if e["name"] == "t/wait"]
    assert ring["args"] == {"bytes": 123, "trace_id": "abc", "admitted": 3}
    host = _host_events(tmp_path)
    assert host["t/wait"] == [{"bytes": 123, "trace_id": "abc",
                               "admitted": 3}]
    assert "t/ring_only" not in host       # add_span writes the ring only


def test_with_neither_switch_span_is_the_shared_null():
    sp = telemetry.span("t/nothing", k=1)
    assert sp is tracer._NULL_SPAN
    with sp as inner:
        inner.set(more=2)                  # a no-op, not an error
    telemetry.add_span("t/nothing", 1, 2)
    telemetry.add_span_s("t/nothing", 1.0, 2.0)
    telemetry.instant("t/nothing")
    assert telemetry.events() == []


def test_explicit_arm_records_without_a_twin():
    telemetry.arm()
    sp = telemetry.span("t/armed")
    assert sp.twin is None
    with sp:
        pass
    assert [e["name"] for e in telemetry.events()] == ["t/armed"]


def test_self_times_follow_containment():
    def ev(name, t0, t1):
        return {"name": name, "t0_ns": t0, "t1_ns": t1}
    spans = [ev("leaf_a", 10, 30), ev("leaf_b", 40, 60),
             ev("mid", 5, 70), ev("root", 0, 100), ev("next", 100, 130),
             {"name": "instant", "t0_ns": 50, "t1_ns": None}]
    got = {e["name"]: own for e, own in telemetry.self_times(spans)}
    assert got == {"root": 35, "mid": 25, "leaf_a": 20, "leaf_b": 20,
                   "next": 30}
    assert sum(got.values()) == 130        # the slice, with no gap in it


# ---------------------------------------------------------------------------
# the LM server's scheduler lane
# ---------------------------------------------------------------------------

def test_lm_scheduler_leaves_iteration_trees_under_a_session(tmp_path):
    eng = _engine()
    eng.generate(_prompt(5), max_new_tokens=3)      # one offline prefill
    offline = eng.stats()["prefills"]
    eng.start()
    _session(tmp_path)
    try:
        streams = [eng.submit(_prompt(3 + i, seed=i), max_new_tokens=6)
                   for i in range(6)]
        for s in streams:
            assert len(s.result(timeout=60)) == 6
    finally:
        jax.profiler.stop_trace()
        eng.stop()
    lane = [e for e in telemetry.events() if e["thread"] == "lm-scheduler"]
    by_name = collections.Counter(e["name"] for e in lane)
    assert by_name["lm/prefill"] == eng.stats()["prefills"] - offline == 6
    assert by_name["lm/prefill_wait"] == 6
    assert (by_name["lm/decode_build"] == by_name["lm/decode_dispatch"]
            == by_name["lm/decode_wait"] == by_name["lm/emit"] > 0)
    assert by_name["lm/iteration"] >= by_name["lm/decode_wait"]

    # one tree per pass: every span but the idle poll lies in an iteration,
    # every prefill in an admission
    def inside(e, parents):
        return any(p["t0_ns"] <= e["t0_ns"] and e["t1_ns"] <= p["t1_ns"]
                   for p in parents)
    iterations = [e for e in lane if e["name"] == "lm/iteration"]
    admits = [e for e in lane if e["name"] == "lm/admit"]
    for e in lane:
        if e["name"] not in ("lm/iteration", "lm/idle_wait"):
            assert inside(e, iterations), e
        if e["name"] == "lm/prefill":
            assert inside(e, admits), e
    assert sum(e["args"]["admitted"] for e in admits) == 6
    wait = next(e for e in lane if e["name"] == "lm/decode_wait")
    assert wait["args"]["bytes"] == 4 * 4             # (max_batch,) int32

    # the self times account for the lane's slice
    own = telemetry.self_times(lane)
    slice_ns = (max(e["t1_ns"] for e in lane)
                - min(e["t0_ns"] for e in lane))
    assert sum(v for _, v in own) == pytest.approx(slice_ns, rel=0.02)

    # ... and the same spans are on the profiler's host plane
    host = _host_events(tmp_path)
    assert len(host["lm/decode_wait"]) == by_name["lm/decode_wait"]
    assert host["lm/decode_wait"][0]["bytes"] == 4 * 4
    assert len(host["lm/prefill"]) == 6


def test_lm_histograms_count_admissions_and_not_generate():
    telemetry.REGISTRY.drop_prefix("LM/")
    eng = _engine()
    eng.generate(_prompt(5), max_new_tokens=4)
    q = telemetry.histogram("LM/queue_wait_ms")
    p = telemetry.histogram("LM/prefill_ms")
    assert q.count == 0 and p.count == 0
    h2d = telemetry.counter("LM/h2d_bytes").value
    d2h = telemetry.counter("LM/d2h_bytes").value
    # generate(): one prefill's token and three decode steps' tokens
    # crossed the boundary
    assert d2h == (1 + 3 * 4) * 4
    assert h2d > 0
    eng.start()
    try:
        for s in [eng.submit(_prompt(4, seed=i), max_new_tokens=3)
                  for i in range(5)]:
            s.result(timeout=60)
    finally:
        eng.stop()
    assert q.count == 5 and p.count == 5
    assert q.min >= 0 and p.min > 0
    assert telemetry.counter("LM/d2h_bytes").value > d2h


def test_with_neither_switch_a_run_records_nothing():
    """The ``--trace 0`` runs: no session, no explicit arm, no event, in
    the trainer and in the server; the driver's lane has its name all the
    same."""
    opt = _toy_optimizer()
    opt.optimize()
    eng = _engine()
    eng.start()
    try:
        eng.submit(_prompt(4), max_new_tokens=4).result(timeout=60)
    finally:
        eng.stop()
    assert telemetry.events() == []
    assert "driver" in {r.name for r in tracer._RINGS}


# ---------------------------------------------------------------------------
# the driver's own capture
# ---------------------------------------------------------------------------

def test_set_trace_profile_arms_the_driver_lane_python_tracer_off(tmp_path):
    opt = _toy_optimizer(iterations=8)
    opt.set_trace_profile(str(tmp_path), start_iteration=3, n_iterations=3)
    opt.optimize()
    assert not telemetry.recording()
    lane = [e for e in telemetry.events() if e["thread"] == "driver"]
    by_name = collections.Counter(e["name"] for e in lane)
    assert by_name["driver/device_step"] == 3
    assert by_name["driver/host_wait"] >= 3
    own = telemetry.self_times(lane)
    slice_ns = (max(e["t1_ns"] for e in lane)
                - min(e["t0_ns"] for e in lane))
    assert 0 < sum(v for _, v in own) <= slice_ns
    host = _host_events(tmp_path)
    assert len(host["driver/device_step"]) == 3
    # the Python tracer marks every Python call with a leading "$"
    assert not [n for n in host if n.startswith("$")]


# ---------------------------------------------------------------------------
# named scopes
# ---------------------------------------------------------------------------

def _scopes(lowered):
    """Every scope path of the lowered program's locations, jit wrappers
    and the primitive's own name cut off."""
    out = set()
    for loc in re.findall(r'loc\("([^"]+)"', lowered.as_text(
            debug_info=True)):
        parts = [p for p in loc.split("/")[:-1]
                 if not re.match(r"^(jit|pjit)\(", p)]
        if parts and not loc.startswith("/"):      # a file's path is no scope
            out.add("/".join(parts))
    return out


LAYER = {"ln1", "qkv", "attn", "out", "ln2", "ffn"}
ENDS = {"embed", "ln_f", "head", "log_softmax"}


def _serving_scopes(which):
    eng = _engine()
    if which == "decode":
        return _scopes(eng._decode_cached.lower(*eng._decode_specs))
    specs = eng._prefill_specs[eng._buckets[0]]
    return _scopes(eng._prefill_cached.lower(*specs))


def _train_scopes(model):
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)
    opt = LocalOptimizer(model, LocalDataSet([]), crit)
    opt.set_precision("bf16")
    method = optim.SGD(0.01, momentum=0.9)
    opt.set_optim_method(method)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model._init_params, key)
    tokens = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    args = (params, jax.eval_shape(method.init_slots, params),
            jax.eval_shape(model._init_state), tokens, tokens,
            method.hyper(), jax.ShapeDtypeStruct(key.shape, key.dtype))
    return _scopes(opt._build_step().lower(*args))


@pytest.mark.parametrize("which,extra", [
    ("decode", {"kv_scatter", "kv_gather"}), ("prefill", {"kv_scatter"})])
def test_serving_steps_carry_the_scope_names(which, extra):
    scopes = _serving_scopes(which)
    want = ENDS | {f"layer{i}/{s}" for i in (0, 1) for s in LAYER | extra}
    assert want <= scopes, sorted(want - scopes)


def test_train_step_carries_the_scope_names():
    scopes = _train_scopes(_lm())
    inner = {"ln1", "attn/qkv", "attn/flash", "attn/out", "ln2",
             "ffn/0_Linear", "ffn/2_Linear"}
    forward = ({"embed", "pos", "ln_f", "head", "log_softmax"}
               | {f"layer{i}/{s}" for i in (0, 1) for s in inner})
    # under value_and_grad the forward is the jvp of its scopes, and the
    # backward pass inherits them by itself as their transpose
    want = ({"jvp(cast_params)", "jvp(loss)", "optimizer_update"}
            | {f"jvp(forward)/{s}" for s in forward}
            | {f"transpose(jvp(forward))/{s}" for s in forward - {"pos"}})
    assert want <= scopes, sorted(want - scopes)


def test_scope_names_do_not_depend_on_what_was_built_before():
    def scopes_of(model):
        x = jnp.ones((4, 8))
        return _scopes(jax.jit(
            lambda p: model.apply(p, x, model.state)[0]).lower(model.params))

    def build():
        return (nn.Sequential()
                .add(nn.Linear(8, 8))
                .add(nn.Sequential().add(nn.Linear(8, 8)).add(nn.Tanh()))
                .add(nn.Linear(8, 4, name="classifier"))
                .add(nn.LogSoftMax().set_name("logits")))
    first = build()
    [nn.Linear(2, 2) for _ in range(5)]        # moves the sequence number
    second = build()
    assert first.children[0].name != second.children[0].name
    want = {"0_Linear", "1_Sequential/0_Linear", "1_Sequential/1_Tanh",
            "classifier", "logits"}
    assert scopes_of(first) == scopes_of(second) == want
    # a decoder built after other models names its scopes alike too
    assert _train_scopes(_lm()) == _train_scopes(_lm(seed=4))
