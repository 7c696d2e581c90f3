"""The reduction from a recorded trace to the device numbers."""
import json
import os

import pytest

from bench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        return trace.Trace.from_json(json.load(f))


def test_busy_is_the_union_inside_the_window(recorded):
    r = trace.reduce(recorded, "bench.window")
    assert r.devices == 1
    assert r.window_s == pytest.approx(1000e-9)
    # [1100, 1400) under the while, [1600, 1700), and the op at
    # [1950, 2100) cut at the window's end; the op before it is out
    assert r.busy_s == pytest.approx((300 + 100 + 50) * 1e-9)


def test_per_op_self_times(recorded):
    r = trace.reduce(recorded, "bench.window")
    # the while's body ops take all of its 300 ns
    assert r.op_s["while.9"] == pytest.approx(0.0)
    assert r.op_s["convolution_add_fusion.1"] == pytest.approx(250e-9)
    assert r.op_s["convolution.7"] == pytest.approx(100e-9)
    assert [name for name, _ in r.top_ops(2)] == [
        "convolution_add_fusion.1", "convolution.7"]


def test_idle_gaps_go_to_the_innermost_host_span(recorded):
    r = trace.reduce(recorded, "bench.window")
    gaps = dict((k, v) for k, v in r.top_gaps())
    # [1000, 1100) under the window alone, [1400, 1600) under "stage" and
    # [1700, 1950) under "sync"; spans of other threads do not count
    assert gaps == pytest.approx({"bench.window": 100e-9, "stage": 200e-9,
                                  "sync": 250e-9})


def test_json_round_trip(recorded):
    again = trace.Trace.from_json(json.loads(json.dumps(recorded.to_json())))
    assert again.planes == recorded.planes


def test_short_names():
    assert trace.short_name("%convolution_add_fusion.3 = f32[64,8,8,64]"
                            "{3,0,2,1} fusion(f32[64] %x), kind=kOutput") \
        == "convolution_add_fusion.3"
    assert trace.short_name("copy-start.2") == "copy-start.2"


def test_a_recorded_v5e_trace():
    """3 ms from the middle of a ``cifar10.avg-t4`` window recorded on a TPU
    v5e (100 device ops), reduced by the same code: inside the scan the
    device is busy throughout, and the ops carry their short HLO names."""
    with open(os.path.join(HERE, "trace_v5e.json")) as f:
        recorded = trace.Trace.from_json(json.load(f))
    r = trace.reduce(recorded, "bench.window")
    assert r.devices == 1
    assert r.window_s == pytest.approx(3e-3)
    assert r.busy_s == pytest.approx(r.window_s)
    assert r.top_gaps() == []
    assert "while.1020" in r.op_s
    assert any(name.startswith("fusion.") for name in r.op_s)
    assert all(" = " not in name for name in r.op_s)


def test_gap_labels_match_the_shortest_covering_span():
    """The sweep that names the gaps gives, for every time, the shortest
    span of the thread that covers it, as a scan of every span would."""
    import random
    rnd = random.Random(7)
    spans = []

    def nest(lo, hi, depth):
        t = lo
        while depth < 4 and t < hi - 4:
            a = rnd.uniform(t, hi - 2)
            b = rnd.uniform(a + 1, hi)
            spans.append((f"s{len(spans)}", a, b - a))
            nest(a, b, depth + 1)
            t = b + rnd.uniform(0, 3)
    nest(0.0, 1000.0, 0)
    times = [rnd.uniform(-5, 1005) for _ in range(500)]

    def shortest(t):
        cover = [e for e in spans if e[1] <= t <= e[1] + e[2]]
        return min(cover, key=lambda e: e[2])[0] if cover else "w"
    assert trace._host_labels(spans, times, "w") == [shortest(t)
                                                      for t in times]
