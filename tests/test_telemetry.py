"""Runtime telemetry subsystem: span tracer, step decomposition,
metrics registry, slow-step detection, and the traced end-to-end train.

The conftest arms the tracer for EVERY tier-1 test (alongside the strict
host-sync guard), so the whole suite doubles as the proof that telemetry
introduces zero device→host syncs; the end-to-end test here additionally
exports the Chrome trace and checks every promised lane is present."""

import io
import json
import os
import threading

import numpy as np
import pytest

from bigdl_tpu import telemetry
from bigdl_tpu.telemetry.metrics import MetricsRegistry
from bigdl_tpu.utils import config


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

class TestSpanTracer:
    def test_nested_spans_record_containment(self):
        telemetry.reset_tracer()
        with telemetry.span("outer/a", tag=1):
            with telemetry.span("inner/b"):
                pass
        evs = {e["name"]: e for e in telemetry.events()}
        assert {"outer/a", "inner/b"} <= set(evs)
        outer, inner = evs["outer/a"], evs["inner/b"]
        assert outer["t0_ns"] <= inner["t0_ns"]
        assert inner["t1_ns"] <= outer["t1_ns"]
        assert outer["args"] == {"tag": 1}
        assert outer["lane"] == inner["lane"]

    def test_cross_thread_spans_land_on_distinct_lanes(self):
        telemetry.reset_tracer()
        with telemetry.span("main/span"):
            pass

        def worker():
            telemetry.name_thread("my-worker")
            with telemetry.span("worker/span"):
                pass

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        evs = telemetry.events()
        lanes = {e["name"]: e["lane"] for e in evs}
        assert lanes["main/span"] != lanes["worker/span"]
        threads = {e["name"]: e["thread"] for e in evs}
        assert threads["worker/span"] == "my-worker"

    def test_disarmed_span_records_nothing(self):
        telemetry.disarm()
        telemetry.reset_tracer()
        with telemetry.span("ghost/span"):
            pass
        telemetry.add_span("ghost/add", 0, 10)
        telemetry.instant("ghost/instant")
        assert telemetry.events() == []
        telemetry.arm(ring_size=4096)   # restore the conftest contract

    def test_ring_is_bounded(self):
        telemetry.disarm()
        telemetry.reset_tracer()
        telemetry.arm(ring_size=8)

        def burst():
            for i in range(100):
                telemetry.add_span(f"s{i}", i, i + 1)

        t = threading.Thread(target=burst)
        t.start()
        t.join()
        names = [e["name"] for e in telemetry.events()]
        assert len(names) == 8
        assert names == [f"s{i}" for i in range(92, 100)]

    def test_chrome_trace_schema(self, tmp_path):
        telemetry.reset_tracer()
        with telemetry.span("cat/span", k="v"):
            pass
        telemetry.instant("cat/marker")
        path = str(tmp_path / "trace.json")
        doc = telemetry.export_chrome_trace(path)
        # the on-disk file is the same JSON document
        assert json.load(open(path)) == json.loads(json.dumps(doc))
        assert isinstance(doc["traceEvents"], list)
        phases = {"X": 0, "M": 0, "i": 0}
        for ev in doc["traceEvents"]:
            assert ev["ph"] in phases
            phases[ev["ph"]] += 1
            assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
            if ev["ph"] == "X":
                assert ev["ts"] >= 0 and ev["dur"] >= 0
                assert ev["cat"] == ev["name"].split("/", 1)[0]
        assert phases["X"] == 1 and phases["i"] == 1
        names = [e for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"]
        assert names, "thread_name metadata missing"


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_histogram_and_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("items", labels={"stage": "decode"})
        c.inc()
        c.inc(4)
        assert reg.counter("items", labels={"stage": "decode"}) is c
        assert c.value == 5
        g = reg.gauge("occupancy")
        g.set(3.5)
        assert g.value == 3.5
        h = reg.histogram("lat", window=16)
        for v in range(10):
            h.observe(v)
        assert h.count == 10 and h.min == 0 and h.max == 9
        with pytest.raises(TypeError):
            reg.gauge("items", labels={"stage": "decode"})

    def test_snapshot_round_trips_through_json(self):
        reg = MetricsRegistry()
        reg.counter("a/count", summary=True).inc(2)
        reg.gauge("b/gauge", labels={"x": "1"}).set(7.25)
        h = reg.histogram("c/hist", window=8)
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        reg.register_provider("p", lambda: [("p/one", 1.5)])
        snap = reg.snapshot()
        restored = json.loads(json.dumps(snap))
        assert restored == snap
        assert restored["counters"]["a/count"] == 2
        assert restored["gauges"]['b/gauge{x=1}'] == 7.25
        assert restored["histograms"]["c/hist"]["count"] == 4
        assert restored["histograms"]["c/hist"]["p50"] == np.percentile(
            [1, 2, 3, 4], 50)
        assert restored["provided"]["p/one"] == 1.5

    def test_summary_scalars_is_the_single_flush_path(self):
        reg = MetricsRegistry()
        reg.gauge("charted", summary=True).set(1.0)
        reg.gauge("uncharted").set(2.0)
        reg.register_provider("prov", lambda: [("prov/a", 3.0)])
        pairs = dict(reg.summary_scalars())
        assert pairs == {"charted": 1.0, "prov/a": 3.0}

    def test_prometheus_text_dump(self):
        reg = MetricsRegistry()
        reg.counter("Ingest/read/items", labels={"engine": "e0"}).inc(9)
        h = reg.histogram("Telemetry/step_latency_ms")
        h.observe(10.0)
        text = reg.prometheus_text()
        assert '# TYPE Ingest_read_items counter' in text
        assert 'Ingest_read_items{engine="e0"} 9.0' in text
        assert '# TYPE Telemetry_step_latency_ms histogram' in text
        assert 'Telemetry_step_latency_ms_count 1' in text
        assert 'le="+Inf"' in text

    def test_drop_prefix(self):
        reg = MetricsRegistry()
        reg.gauge("Telemetry/x", summary=True).set(1)
        reg.gauge("Other/y", summary=True).set(2)
        reg.drop_prefix("Telemetry/")
        assert dict(reg.summary_scalars()) == {"Other/y": 2.0}


# ---------------------------------------------------------------------------
# step stats: percentiles, decomposition, slow-step detection
# ---------------------------------------------------------------------------

class TestStepStats:
    def test_windowed_percentiles_match_numpy(self):
        rng = np.random.RandomState(7)
        values = rng.lognormal(3.0, 1.0, size=300)
        wp = telemetry.WindowedPercentiles(window=64)
        for v in values:
            wp.add(v)
        window = values[-64:]
        for q in (50, 90, 95, 99):
            assert wp.percentile(q) == pytest.approx(
                float(np.percentile(window, q)), rel=1e-12)

    def test_percentiles_empty_and_partial_window(self):
        wp = telemetry.WindowedPercentiles(window=8)
        assert np.isnan(wp.percentile(50))
        wp.add(5.0)
        assert wp.percentile(99) == 5.0

    def test_decomposition_sums_to_wall_exactly(self):
        telemetry.REGISTRY.drop_prefix("Telemetry/")
        acct = telemetry.StepAccount(window=16)
        rng = np.random.RandomState(0)
        for _ in range(20):
            wall = int(rng.randint(1_000_000, 50_000_000))
            parts = {p: float(rng.randint(0, wall // 4))
                     for p in telemetry.PARTS}
            acct.account(wall, **parts)
            total = sum(acct.last[p] for p in telemetry.PARTS)
            total += acct.last["unaccounted"]
            assert total == pytest.approx(wall, rel=1e-9)
        s = acct.summary()
        assert s["steps"] == 20
        closure = sum(s[f"{p}_frac"] for p in
                      telemetry.PARTS + ("unaccounted",))
        assert closure == pytest.approx(1.0, rel=1e-9)
        # the decomposition gauges ride the single flush path
        pairs = dict(telemetry.summary_scalars())
        assert "Telemetry/step_ms" in pairs
        for p in telemetry.PARTS:
            assert f"Telemetry/{p}_ms" in pairs

    def test_slow_step_detector_fires_once_per_anomaly_window(self):
        det = telemetry.SlowStepDetector(factor=3.0, warmup=3, cooldown=0)
        fired = [det.observe(100.0) for _ in range(6)]
        assert fired == [False] * 6
        # one sustained anomaly window: fires on entry, not per step
        burst = [det.observe(1000.0) for _ in range(5)]
        assert burst == [True, False, False, False, False]
        assert det.fired == 1
        # back to normal closes the window; a second window fires again
        assert det.observe(100.0) is False
        assert det.observe(1000.0) is True
        assert det.fired == 2

    def test_slow_step_detector_cooldown_separates_windows(self):
        det = telemetry.SlowStepDetector(factor=2.0, warmup=2, cooldown=3)
        for _ in range(4):
            det.observe(100.0)
        assert det.observe(500.0) is True
        assert det.observe(100.0) is False       # cooldown 3 -> 2
        assert det.observe(500.0) is False       # within cooldown: held
        for _ in range(3):
            det.observe(100.0)                   # cooldown expires
        assert det.observe(500.0) is True
        assert det.fired == 2

    def test_detector_disabled_and_ema_tracks_healthy_regime(self):
        assert telemetry.SlowStepDetector(0.0).observe(1e9) is False
        det = telemetry.SlowStepDetector(factor=2.0, warmup=1, cooldown=0)
        for _ in range(10):
            det.observe(100.0)
        ema_before = det.ema
        det.observe(10_000.0)                    # anomaly: EMA untouched
        assert det.ema == ema_before


# ---------------------------------------------------------------------------
# the traced tier-1 train: every lane, decomposition against wall time,
# registry-routed scalars with unchanged tags
# ---------------------------------------------------------------------------

def _jpeg_records(n=16, hw=(36, 36)):
    from PIL import Image

    from bigdl_tpu.dataset.image import LabeledImageBytes
    rng = np.random.RandomState(5)
    recs = []
    for i in range(n):
        img = rng.randint(0, 256, size=hw + (3,)).astype(np.uint8)
        buf = io.BytesIO()
        Image.fromarray(img).save(buf, "JPEG", quality=90)
        recs.append(LabeledImageBytes(f"r{i}", float(i % 4 + 1),
                                      buf.getvalue()))
    return recs


def test_traced_train_exports_all_lanes_and_decomposition(tmp_path):
    """A 3-step tier-1 train with telemetry armed end to end: streaming
    ingest + prefetcher + async checkpointing, strict retrace AND strict
    host-sync guards on (conftest).  Proves: (a) telemetry adds zero
    host syncs; (b) the exported Chrome trace carries driver, ingest,
    prefetcher, and checkpoint-writer lanes; (c) the step decomposition
    sums to the charted wall step time; (d) Ingest/* scalars arrive with
    unchanged tags through the registry's single flush path."""
    import jax

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import LocalDataSet
    from bigdl_tpu.dataset.ingest import StreamingIngest
    from bigdl_tpu.visualization import TrainSummary

    trace_path = str(tmp_path / "trace.json")
    config.set_property("bigdl.telemetry.tracePath", trace_path)
    config.set_property("bigdl.telemetry.snapshotPath", str(tmp_path))
    try:
        recs = _jpeg_records(n=16)
        ds = LocalDataSet(recs).transform(
            StreamingIngest(4, crop=(32, 32), decode_workers=2,
                            name="teleingest"))
        model = (nn.Sequential().add(nn.Reshape((3 * 32 * 32,)))
                 .add(nn.Linear(3 * 32 * 32, 4)).add(nn.LogSoftMax()))
        model.reset(jax.random.PRNGKey(3))
        opt = optim.Optimizer.create(model, ds, nn.ClassNLLCriterion())
        opt.set_optim_method(optim.SGD(learning_rate=0.05))
        opt.set_end_when(optim.max_iteration(3))
        opt.set_checkpoint(str(tmp_path / "ckpt"),
                           optim.several_iteration(1), async_write=True)
        ts = TrainSummary(str(tmp_path), "tele")
        opt.set_train_summary(ts)
        opt.optimize()
    finally:
        config.clear_property("bigdl.telemetry.tracePath")
        config.clear_property("bigdl.telemetry.snapshotPath")

    # (b) every promised lane shows up in the exported timeline
    doc = json.load(open(trace_path))
    lanes = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "driver" in lanes
    assert any(l.startswith("ingest-reader") for l in lanes), lanes
    assert any(l.startswith("ingest-assembler") for l in lanes), lanes
    assert any(l.startswith("ingest-decode") for l in lanes), lanes
    assert any(l.startswith("prefetch-fetch") for l in lanes), lanes
    assert any(l.startswith("bigdl-ckpt-writer") for l in lanes), lanes
    span_names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"driver/fetch", "driver/device_step", "driver/host_wait",
            "driver/checkpoint", "ingest/decode",
            "ingest/assemble"} <= span_names, span_names
    assert "checkpoint/write" in span_names

    # (c) per-step decomposition sums to the charted wall step time
    step_ms = dict(ts.read_scalar("Telemetry/step_ms"))
    assert len(step_ms) == 3
    parts = {p: dict(ts.read_scalar(f"Telemetry/{p}_ms"))
             for p in telemetry.PARTS + ("unaccounted",)}
    for neval, wall in step_ms.items():
        total = sum(parts[p][neval] for p in parts)
        assert total == pytest.approx(wall, rel=0.05), (neval, total, wall)
    # rolling latency percentiles charted too
    assert len(ts.read_scalar("Telemetry/step_p50_ms")) == 3
    assert len(ts.read_scalar("Telemetry/step_p99_ms")) == 3

    # (d) Ingest/* scalars still arrive, tags unchanged, via the registry
    thr = ts.read_scalar("Ingest/teleingest/consume/throughput")
    assert thr, "Ingest/* scalars must survive the registry migration"
    # sanitizer scalars kept their historical tags as well
    assert len(ts.read_scalar("Analysis/retraces")) == 3
    assert len(ts.read_scalar("Analysis/implicit_host_syncs")) == 3

    # per-run registry snapshot landed next to the trace
    snap = json.load(open(tmp_path / "telemetry.json"))
    assert snap["step_summary"]["steps"] == 3
    assert "Telemetry/step_latency_ms" in snap["histograms"]


def test_slow_step_capture_writes_profile_and_timeline(tmp_path):
    """A forced-slow iteration fires the detector once, dumps the
    timeline, and triggers a one-shot on-demand jax.profiler capture."""
    import jax

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import LocalDataSet, SampleToMiniBatch
    from bigdl_tpu.dataset.datasets import synthetic_separable

    prof_dir = tmp_path / "slow"
    config.set_property("bigdl.telemetry.slowStepFactor", 5.0)
    config.set_property("bigdl.telemetry.slowStepWarmup", 3)
    config.set_property("bigdl.telemetry.slowStepCooldown", 2)
    config.set_property("bigdl.telemetry.profileOnSlowStep", str(prof_dir))
    # a short dispatch pipeline so the anomaly DRAINS while the loop is
    # still running (at the default depth 8 a 12-step run retires the
    # slow interval only in the final flush, after the capture window)
    config.set_property("bigdl.pipeline.depth", 2)
    try:
        samples = synthetic_separable(64, 8, n_classes=2, seed=2)
        base = LocalDataSet(samples).transform(SampleToMiniBatch(16))

        class Stall:
            """One artificially slow fetch, well past warmup."""
            def __init__(self):
                self.n = 0

            def __call__(self, it):
                import time as _time
                for b in it:
                    self.n += 1
                    if self.n == 8:
                        _time.sleep(0.5)
                    yield b

        ds = base.transform(Stall())
        model = (nn.Sequential().add(nn.Linear(8, 4)).add(nn.LogSoftMax()))
        model.reset(jax.random.PRNGKey(1))
        opt = optim.Optimizer.create(model, ds, nn.ClassNLLCriterion())
        opt.set_optim_method(optim.SGD(learning_rate=0.1))
        opt.set_end_when(optim.max_iteration(12))
        opt.optimize()
        acct = opt._step_account
        assert acct.detector.fired >= 1
        dumps = [f for f in os.listdir(prof_dir)
                 if f.startswith("slowstep_") and f.endswith(".json")]
        assert dumps, "timeline dump missing"
        json.load(open(prof_dir / dumps[0]))       # well-formed
        assert (prof_dir / "slowstep_profile").is_dir(), \
            "on-demand jax.profiler capture missing"
    finally:
        for k in ("slowStepFactor", "slowStepWarmup", "slowStepCooldown",
                  "profileOnSlowStep"):
            config.clear_property(f"bigdl.telemetry.{k}")
        config.clear_property("bigdl.pipeline.depth")
