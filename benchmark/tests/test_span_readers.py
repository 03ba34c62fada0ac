"""The span and counter readers on hand-built rings: each returns the
number worked out by hand from known intervals, and nothing on an empty
lane or a missing histogram."""

import threading

import pytest

from benchmark import cells

MS = 1_000_000


@pytest.fixture
def telemetry():
    from bigdl_tpu import telemetry
    telemetry.reset_tracer()
    telemetry.REGISTRY.reset()
    telemetry.arm()
    yield telemetry
    telemetry.disarm()
    telemetry.reset_tracer()
    telemetry.REGISTRY.reset()


def _fill(telemetry, thread_name, spans):
    """Record ``[(name, start_ms, end_ms)]`` on a lane of that name."""
    def run():
        for name, t0, t1 in spans:
            telemetry.add_span(name, t0 * MS, t1 * MS)
    t = threading.Thread(target=run, name=thread_name)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()


# two scheduler passes and an idle poll: the slice runs from 100 to 200 ms
SCHEDULER = [
    ("lm/admit", 100, 112), ("lm/prefill", 101, 111),
    ("lm/prefill_wait", 103, 111),
    ("lm/decode_build", 112, 113), ("lm/decode_dispatch", 113, 115),
    ("lm/decode_wait", 115, 140), ("lm/emit", 140, 142),
    ("lm/iteration", 100, 142),
    ("lm/idle_wait", 142, 152),
    ("lm/decode_build", 152, 153), ("lm/decode_dispatch", 153, 155),
    ("lm/decode_wait", 155, 194), ("lm/emit", 194, 200),
    ("lm/iteration", 152, 200),
]


def _read(name):
    with open(f"{cells.HERE}/metrics/tracer.{name}.json") as f:
        metric = __import__("json").load(f)
    assert metric["name"] == name
    return cells.reader(metric["reader"])({}, metric)


def test_scheduler_span_readers(telemetry):
    _fill(telemetry, "lm-scheduler", SCHEDULER)
    # waits: 8 (prefill) + 25 + 39 (decode) + 10 (idle) = 82 of 100 ms
    assert _read("sched_host_share.serve") == pytest.approx(18.0)
    assert _read("decode_wait_ms.serve") == pytest.approx(32.0)   # (25+39)/2
    assert _read("emit_ms.serve") == pytest.approx(4.0)           # (2+6)/2
    # another lane's spans are not the scheduler's
    assert _read("driver_busy.train") is None


def test_driver_busy(telemetry):
    _fill(telemetry, "driver", [
        ("driver/fetch", 10, 11), ("driver/device_step", 11, 13),
        ("driver/host_wait", 13, 45), ("driver/summary", 45, 46),
        ("driver/fetch", 46, 47), ("driver/device_step", 47, 50),
        ("driver/host_wait", 50, 90)])
    # 72 of the slice's 80 ms wait for a loss
    assert _read("driver_busy.train") == pytest.approx(10.0)
    assert _read("sched_host_share.serve") is None


def test_histogram_readers(telemetry):
    q = telemetry.histogram("LM/queue_wait_ms")
    p = telemetry.histogram("LM/prefill_ms")
    for v in range(1, 12):              # 1 .. 11
        q.observe(float(v))
        p.observe(10.0 * v)
    assert _read("queue_wait_p90_ms.serve") == pytest.approx(10.0)
    assert _read("prefill_p50_ms.serve") == pytest.approx(60.0)


@pytest.mark.parametrize("name", [
    "sched_host_share.serve", "decode_wait_ms.serve", "emit_ms.serve",
    "driver_busy.train", "prefill_p50_ms.serve", "queue_wait_p90_ms.serve"])
def test_nothing_recorded_reads_nothing(telemetry, name):
    assert _read(name) is None


def test_a_program_from_before_the_histograms(telemetry):
    """The parent commit keeps ``LM/prefill_ms`` as a gauge and has no
    ``LM/queue_wait_ms``: the readers leave the metrics out, they do not
    raise."""
    telemetry.gauge("LM/prefill_ms").set(17.0)
    assert _read("prefill_p50_ms.serve") is None
    assert _read("queue_wait_p90_ms.serve") is None
