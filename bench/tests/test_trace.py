"""Reduction of a trace to per-layer metrics, on a synthetic .xplane."""

import numpy as np
import pytest

from lib import roofline, runner, trace
from run import breakdown, read_metric

# us; one device op line and the bench's host spans, as jax.profiler
# writes them: events are (metadata_id, offset_ps, duration_ps) on lines
# that start at timestamp_ns
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 13000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 4000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 40000000 } }
  event_metadata { key: 1 value { id: 1 name: "gather.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_get" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 5 offset_ps: 21000000 duration_ps: 19000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.round" } }
  event_metadata { key: 3 value { id: 3 name: "bench.get" } }
  event_metadata { key: 4 value { id: 4 name: "backend_compile" } }
  event_metadata { key: 5 value { id: 5 name: "bench.update" } }
}
"""


@pytest.fixture(scope="module")
def tr():
    from jax.profiler import ProfileData
    return trace.from_profile(ProfileData.from_text_proto(XSPACE))


def test_load_reads_device_ops_and_bench_spans(tr):
    assert tr.n_devices == 1
    assert [(n, round(s * 1e6), round(e * 1e6)) for n, s, e, _ in tr.ops] == [
        ("gather.1", 1, 3), ("fusion.2", 2, 4), ("gather.1", 13, 14),
        ("fusion.2", 30, 34)]
    assert tr.span("bench.window") == [(0.0, 40e-6)]
    assert len(tr.span("bench.get")) == 2
    assert [n for n, *_ in tr.host] == ["backend_compile"]


def test_union_and_idle_share(tr):
    (starts, ends), = tr.busy()
    np.testing.assert_allclose(starts * 1e6, [1, 13, 30])
    np.testing.assert_allclose(ends * 1e6, [4, 14, 34])
    # busy 3 + 1 + 4 = 8 ns of a 40 us window
    assert trace.covered((starts, ends), 0, 40e-6) == pytest.approx(8e-6)
    run = _run(tr)
    assert read_metric("device_idle_share", run) == pytest.approx(80.0)


def test_span_overlap_attribution(tr):
    run = _run(tr)
    # Get spans [0,10] and [10,20] us hold device time 3 + 1 = 4 ns over
    # 2 calls of 10 us: host 8 us per call, device 4 us per 8 Gets
    assert read_metric("get_host_ms", run) == pytest.approx(8e-6 * 1e3)
    assert read_metric("get_device_us_per_kget", run) == pytest.approx(
        4e-6 / 8 * 1e3 * 1e6)


def test_roofline_with_its_byte_count(tr):
    run = _run(tr, meter={"cache_hits": 3, "cache_neg_hits": 1})
    # 8 Gets less 4 cache answers reach the device: 4 x 50 B in 4 us
    want = 100 * roofline.get_bytes(4) / 819e9 / 4e-6
    assert read_metric("get_roofline", run) == pytest.approx(want)


def test_breakdown_names_ops_and_gaps(tr):
    bd = breakdown(_run(tr))
    assert bd["device_ops"][0][0] == "fusion.2"
    assert bd["device_ops"][0][1] == pytest.approx(6e-6)
    label, gap = bd["idle_gaps"][0]
    assert label == "bench.update" and gap == pytest.approx(16e-6)
    # the gap in the first Get holds the compile
    assert bd["idle_gaps"][1] == ["bench.get/backend_compile",
                                  pytest.approx(9e-6)]


def test_gaps_cover_the_rest_of_the_window():
    merged = trace.union([(1, 2), (1.5, 3), (5, 6)])
    assert trace.gaps(merged, 0, 10) == [(0, 1), (3, 5), (6, 10)]
    assert trace.gaps(trace.union([]), 0, 1) == [(0, 1)]


def _run(tr, meter=None):
    calls = [runner.Call("get", 4, 0.0, 0.0, 0.0)] * 2
    return runner.Run(
        cell="c", seed=0, config={}, traffic={},
        peaks={"hbm_bytes_per_s": 819e9}, setup_s=1.0, build_s=0.5,
        window_start=0.0, window_end=1.0, calls=calls, wrong={"get": 0},
        compared={"get": 8}, compiles=[],
        meter=meter or {"cache_hits": 0, "cache_neg_hits": 0},
        trace=tr)
