"""``trace_reduce`` on a recorded TPU trace and on a synthetic two-chip one.

``data/tiny.xplane.pb`` was recorded on a TPU v5 lite by
``record_trace.py`` (PR 23): three bursts of four ``tanh(x @ x)`` fusions
with a copy before them, a 20 ms host sleep after the first burst and a
50 ms sleep inside a ``bench/sleep`` span after the second.  The figures
below were worked out by hand from the event list.
"""

import os
import re

import pytest

from benchmark import trace_reduce as tr

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def tiny():
    return tr.read_events(TINY)


def test_tiny_events(tiny):
    ops = tiny["devices"][0]
    assert len(ops) == 18 and list(tiny["devices"]) == [0]
    assert ops[0][:2] == (45403318, 45403332)
    assert ops[-1][:2] == (118574513, 118587134)
    assert ops[2][2] == "convolution_tanh_fusion.3 bf16[1024,1024]"
    assert tiny["spans"] == [(69066736, 119436364, "bench/sleep")]


def test_tiny_busy_idle_gaps(tiny):
    r = tr.reduce_events(tiny, kernel=re.compile(r"^convolution_tanh"))
    # slice: first start 45,403,318 to last end 118,587,134
    assert r["window_s"] == pytest.approx(73183816e-9, abs=1e-12)
    # bursts: 47,361 + 47,360 + 47,359 ns of operations back to back
    assert r["busy_s"] == pytest.approx(142080e-9, abs=1e-12)
    assert r["idle_share_worst"] == pytest.approx(
        1 - 142080 / 73183816, abs=1e-9)
    # the two sleeps, the longer one under the benchmark's span
    assert r["idle_gaps"][0] == ["bench/sleep", pytest.approx(51172782e-9)]
    assert r["idle_gaps"][1] == ["inside-program",
                                 pytest.approx(21868938e-9)]
    # twelve fusions: 47,344 + 47,344 + 47,343 ns
    assert r["kernel_events"] == 12
    assert r["kernel_s"] == pytest.approx(142031e-9, abs=1e-12)
    assert r["device_ops"][0][0] == "convolution_tanh_fusion bf16[1024,1024]"
    assert r["collective_exposed_share_worst"] == 0.0


TWO_CHIPS = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 3000000 }
  }
  lines { id: 2 name: "Async XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 6000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %fusion.1)" } }
  event_metadata { key: 3 value { id: 3 name: "%all-gather-start.2 = (f32[2]{0}, f32[8]{0}) all-gather-start(f32[2]{0} %q)" } }
}
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%reduce-scatter.4 = f32[2]{0} reduce-scatter(f32[8]{0} %fusion.1)" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/submit" } }
}
"""


def test_two_chip_collective_exposure(tmp_path):
    """Chip 0 (times in ns from 1000): fusion 0-4000, all-reduce
    4000-6000, idle 6000-7000, fusion 7000-10000, and an all-gather
    window 2000-8000 on the async line.  Collectives cover 2000-8000;
    other work covers 0-4000 and 7000-10000, so 4000-7000 is exposed:
    3000 of the 10000 ns slice.  Chip 1: fusion 0-9000, reduce-scatter
    9000-10000, all exposed: 1000."""
    from jax.profiler import ProfileData
    path = tmp_path / "two.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TWO_CHIPS))
    ev = tr.read_events(str(path))
    assert sorted(ev["devices"]) == [0, 1]
    assert [n for _, _, n in ev["async"][0]] == [
        "all-gather-start.2 (f32[2], f32[8])"]
    r = tr.reduce_events(ev)
    assert r["chips"] == 2
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["collective_exposed_share_worst"] == pytest.approx(0.3)
    assert r["collective_share_worst"] == pytest.approx(0.6)
    # busy: chip 0 9000 (the async window is not an operation), chip 1
    # 10000; the mean is reported, the worst idle share is chip 0's
    assert r["busy_s"] == pytest.approx(9500e-9)
    assert r["idle_share_worst"] == pytest.approx(0.1)
    assert r["idle_gaps"][0] == ["bench/submit", pytest.approx(1000e-9)]


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                        (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.total([(0, 10), (20, 30)]) == 20


def test_op_label():
    text = ("%fusion.10 = (pred[]{:T(512)}, bf16[4096,50272]{0,1:T(8,128)"
            "(2,1)}) fusion(bf16[4,2048,4096]{2,1,0} %x), kind=kOutput")
    assert tr.op_label(text) == "fusion.10 (pred[], bf16[4096,50272])"
    assert tr.op_label("bench/submit") == "bench/submit"
