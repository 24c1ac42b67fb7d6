"""Readers of the program's own spans and counters, on a synthetic trace
and synthetic counter samples."""

import sys
import types

import pytest

from lib import runner, trace
import test_run
from run import read_metric
from test_run import off_chip  # noqa: F401  (fixture)

# ms; two Get calls and one update call, the program's spans inside them
# and one outside any call (set-up work, which no reader may count)
GET_CALLS = [(0.0, 10.0), (20.0, 32.0)]
UPDATE_CALLS = [(10.0, 18.0)]
HOST = [
    ("repro.cache.probe", 0.0, 1.0),
    ("repro.get.upload", 1.0, 3.0),
    ("repro.get.dispatch", 3.0, 6.0),
    ("PjitFunction(add)", 3.5, 4.0),  # a JAX event inside the dispatch
    ("repro.get.fetch", 6.0, 8.0),
    ("repro.cache.observe", 8.0, 9.5),
    ("repro.cache.note", 11.0, 15.0),
    ("repro.get.upload", 18.5, 19.5),  # between calls
    ("repro.cache.probe", 20.0, 21.0),
    ("repro.get.upload", 21.0, 22.0),
    ("repro.get.dispatch", 22.0, 26.0),
    ("repro.get.fetch", 26.0, 27.0),
    ("repro.get.makeup", 27.0, 29.0),
    ("repro.get.upload", 29.0, 29.5),
    ("repro.get.fetch", 29.5, 30.0),
    ("repro.cache.observe", 30.0, 31.0),
]


def _trace(host=HOST):
    ms = 1e-3
    return trace.Trace(
        ops=[], n_devices=1,
        spans={"bench.get": [(s * ms, e * ms) for s, e in GET_CALLS],
               "bench.update": [(s * ms, e * ms) for s, e in UPDATE_CALLS]},
        host=sorted(((n, s * ms, e * ms) for n, s, e in host),
                    key=lambda ev: ev[1]))


def _run(tr, gets=2_000, updates=100, window=(100.0, 200.0)):
    calls = [runner.Call("get", gets // 2, 0.0, 0.0, 0.0)] * 2 + [
        runner.Call("update", updates, 0.0, 0.0, 0.0)]
    return runner.Run(
        cell="c", seed=0, config={}, traffic={}, peaks=None, setup_s=1.0,
        build_s=0.5, window_start=window[0], window_end=window[1],
        calls=calls, wrong={"get": 0}, compared={"get": gets}, compiles=[],
        meter={"cache_hits": 0, "cache_neg_hits": 0}, trace=tr)


@pytest.mark.parametrize("metric,want", [
    # ms per Get call
    ("get_upload_ms", (2 + 1 + 0.5) / 2),
    ("get_upload_ms.cn_cache", (2 + 1 + 0.5) / 2),
    ("get_dispatch_ms", (3 + 4) / 2),
    ("get_fetch_ms", (2 + 1 + 0.5) / 2),
    ("get_makeup_ms", 2 / 2),
    ("cn_cache_ms", (1 + 1.5 + 1 + 1) / 2),
    # ms per 1,000 updates
    ("update_cache_ms_per_kop", 4 / 100 * 1e3),
])
def test_span_readers(metric, want):
    assert read_metric(metric, _run(_trace())) == pytest.approx(want)


def test_spans_outside_the_calls_are_not_read():
    host = HOST + [("repro.get.dispatch", 40.0, 50.0),
                   ("repro.get.dispatch", 9.0, 12.0)]  # crosses a call's end
    got = read_metric("get_dispatch_ms", _run(_trace(host)))
    assert got == pytest.approx((3 + 4) / 2)


def test_a_program_without_the_span_reads_zero_if_it_has_others():
    host = [ev for ev in HOST if ev[0] != "repro.get.makeup"]
    assert read_metric("get_makeup_ms", _run(_trace(host))) == 0.0


SPAN_METRICS = ["get_upload_ms", "get_dispatch_ms", "get_fetch_ms",
                "get_makeup_ms", "cn_cache_ms", "update_cache_ms_per_kop"]


@pytest.mark.parametrize("metric", SPAN_METRICS)
@pytest.mark.parametrize("case", ["no trace", "no program span"])
def test_span_readers_read_none_without_program_spans(metric, case):
    if case == "no trace":
        run = _run(None)
    else:  # a program older than its spans: only JAX's own events
        run = _run(_trace([("PjitFunction(add)", 3.5, 4.0),
                           ("DevicePut", 12.0, 13.0)]))
    assert read_metric(metric, run) is None


@pytest.fixture
def counters(monkeypatch):
    """A fresh ``repro.obs.wall`` counter state on a clock the test sets."""
    from repro.obs import wall
    now = [0.0]
    monkeypatch.setattr(wall, "time",
                        types.SimpleNamespace(perf_counter=lambda: now[0]))
    monkeypatch.setattr(wall, "_rings", {})
    monkeypatch.setattr(wall, "_totals", {})
    monkeypatch.setattr(wall, "_dropped", set())

    def count(t, name, n):
        now[0] = t
        wall.count(name, n)
    return wall, count


@pytest.mark.parametrize("metric,name,want", [
    ("h2d_bytes_per_get", "get.h2d_bytes", (500 + 700) / 2_000),
    ("h2d_bytes_per_get.cn_cache", "get.h2d_bytes", (500 + 700) / 2_000),
    ("makeup_lanes_per_kget", "get.makeup_lanes", (500 + 700) / 2_000 * 1e3),
])
def test_counter_readers(counters, metric, name, want):
    _, count = counters
    count(50.0, name, 1_000)  # warm-up, before the window
    count(120.0, name, 500)
    count(180.0, name, 700)
    count(250.0, name, 9_000)  # after the window
    assert read_metric(metric, _run(None)) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["h2d_bytes_per_get",
                                    "makeup_lanes_per_kget"])
@pytest.mark.parametrize("case", ["never counted", "ring truncated",
                                  "no such module"])
def test_counter_readers_read_none(counters, monkeypatch, metric, case):
    wall, count = counters
    name = "get.h2d_bytes" if metric.startswith("h2d") else "get.makeup_lanes"
    if case == "ring truncated":
        monkeypatch.setattr(wall, "RING_SAMPLES", 2)
        for t in (90.0, 110.0, 150.0, 190.0):  # keeps those at 150 and 190
            count(t, name, 10)
    if case == "no such module":  # a program older than its counters
        count(150.0, name, 10)
        monkeypatch.setitem(sys.modules, "repro.obs.wall", None)
        monkeypatch.delattr(sys.modules["repro.obs"], "wall")
    assert read_metric(metric, _run(None)) is None


NEW = {"outback-2p24.ycsb-c.uniform": [
           "get_upload_ms", "get_dispatch_ms", "get_fetch_ms",
           "h2d_bytes_per_get"],
       "outback-cncache-2p23.ycsb-b.zipf99": [
           "get_upload_ms.cn_cache", "get_dispatch_ms.cn_cache",
           "get_fetch_ms.cn_cache", "h2d_bytes_per_get.cn_cache",
           "get_makeup_ms", "makeup_lanes_per_kget", "cn_cache_ms",
           "update_cache_ms_per_kop"]}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_traced_run_reads_every_program_metric(off_chip, capsys, cell):
    """The whole traced run off the chip (as in test_run.py): every metric
    of the program's spans and counters is read, within the Get span."""
    program_store, stores = runner.program_store, []

    def keep(spec, keys, values):
        stores.append(program_store(spec, keys, values))
        return stores[-1]

    off_chip.setattr(runner, "program_store", keep)
    out = test_run._result(capsys, cell, trace=1)
    got = {m: out["metrics"].get(m, {}).get("value") for m in NEW[cell]}
    assert None not in got.values(), got
    # the CPU has no device plane in the trace, so get_host_ms is the whole
    # Get span per call, which the program's sibling spans cannot exceed
    per_get = [v for m, v in got.items()
               if m.endswith("_ms") and not m.startswith("update")]
    host = [v["value"] for m, v in out["metrics"].items()
            if m.startswith("get_host_ms")]
    assert 0 < sum(per_get) <= host[0]
    if cell.startswith("outback-2p24"):
        engine = stores[0].engine
        sent = sum(a.nbytes for a in engine._cn_host() + engine._mn_host())
        assert got["h2d_bytes_per_get"] == sent / 8192 + 8
