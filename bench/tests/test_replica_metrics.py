"""Readers of the program's replica-set and retry spans and counters, on a
synthetic trace, synthetic counter samples and whole traced runs off the
chip."""

import json

import numpy as np
import pytest

from lib import runner, trace
import readback
import run
import test_run
from run import read_metric
from test_program_spans import _run, counters  # noqa: F401  (fixture)
from test_run import off_chip  # noqa: F401  (fixture)

R2_CELL = "outback-r2-2p23.ycsb-a.zipf99"
A_CELL = "outback-2p24.ycsb-a.zipf99"
WINDOW = (0.0, 40.0)  # ms
GET_CALLS = [(0.0, 10.0), (20.0, 32.0)]
UPDATE_CALLS = [(10.0, 18.0), (32.0, 39.0)]
HOST = [
    ("repro.replica.failover", 0.5, 1.0),
    ("repro.retry.backoff", 1.0, 9.0),
    ("repro.get.upload", 1.5, 3.0),  # the retried Get, in the backoff span
    ("repro.replica.fanout", 12.0, 15.0),
    ("repro.replica.resync", 20.5, 24.0),
    ("repro.replica.fanout", 33.0, 34.5),
    ("repro.replica.fanout", 18.5, 19.5),  # between calls
    ("repro.replica.resync", 41.0, 45.0),  # after the window
]


def _trace(host=HOST):
    ms = 1e-3
    return trace.Trace(
        ops=[], n_devices=1,
        spans={"bench.window": [(WINDOW[0] * ms, WINDOW[1] * ms)],
               "bench.get": [(s * ms, e * ms) for s, e in GET_CALLS],
               "bench.update": [(s * ms, e * ms) for s, e in UPDATE_CALLS]},
        host=sorted(((n, s * ms, e * ms) for n, s, e in host),
                    key=lambda ev: ev[1]))


REPLICA_COUNTERS = ("replica.failovers", "replica.resyncs",
                    "retry.backoff_lanes")


@pytest.fixture
def replicated(counters):
    """The program's replica counters, each counted once in the window."""
    _, count = counters
    for name in REPLICA_COUNTERS:
        count(150.0, name, 1)
    return counters


@pytest.mark.parametrize("metric,want", [
    ("fanout_ms_per_kop.r2", (3 + 1.5) / 100 * 1e3),  # ms per 1,000 updates
    ("failover_ms.r2", 0.5 + 8),
    ("resync_ms.r2", 3.5),
])
def test_span_readers(replicated, metric, want):
    assert read_metric(metric, _run(_trace())) == pytest.approx(want)


def test_spans_read_in_update_calls_too(replicated):
    """A restart first seen by an update call resyncs (and fails back)
    there."""
    host = [ev for ev in HOST if ev[0] != "repro.replica.resync"]
    host += [("repro.replica.resync", 33.5, 36.0),
             ("repro.replica.failover", 36.0, 36.5)]
    tr = _trace(host)
    assert read_metric("resync_ms.r2", _run(tr)) == pytest.approx(2.5)
    assert read_metric("failover_ms.r2", _run(tr)) == pytest.approx(9.0)


def test_spans_that_never_opened_read_zero(replicated):
    host = [ev for ev in HOST if not ev[0].startswith(("repro.replica.",
                                                       "repro.retry."))]
    host.append(("repro.get.upload", 11.0, 12.0))  # in an update call
    tr = _trace(host)
    assert [read_metric(m, _run(tr)) for m in
            ("fanout_ms_per_kop.r2", "failover_ms.r2", "resync_ms.r2")] == \
        [0.0, 0.0, 0.0]


@pytest.mark.parametrize("metric", ["fanout_ms_per_kop.r2", "failover_ms.r2",
                                    "resync_ms.r2",
                                    "backoff_lanes_per_kop.r2"])
def test_an_older_program_reads_none(counters, metric):
    """The program as it was before the replica set had spans and
    counters: its Get spans alone, and none of the replica counters."""
    _, count = counters
    count(150.0, "get.h2d_bytes", 4096)
    host = [ev for ev in HOST if not ev[0].startswith(("repro.replica.",
                                                       "repro.retry."))]
    assert read_metric(metric, _run(_trace(host))) is None


@pytest.mark.parametrize("metric", ["fanout_ms_per_kop.r2", "failover_ms.r2",
                                    "resync_ms.r2"])
def test_span_readers_read_none_without_a_trace(replicated, metric):
    assert read_metric(metric, _run(None)) is None


@pytest.mark.parametrize("window_lanes,want", [
    ([4096], 4096 / 2_100 * 1e3),  # one Get call hit the window
    ([], 0.0),  # no crash in the window
])
def test_backoff_lanes_per_kop(counters, window_lanes, want):
    _, count = counters
    count(50.0, "retry.backoff_lanes", 8)  # before the window
    for lanes in window_lanes:
        count(150.0, "retry.backoff_lanes", lanes)
    count(250.0, "retry.backoff_lanes", 8)  # after it
    assert read_metric("backoff_lanes_per_kop.r2", _run(None)) == \
        pytest.approx(want)


def test_backoff_lanes_read_none_on_a_truncated_ring(counters, monkeypatch):
    wall, count = counters
    monkeypatch.setattr(wall, "RING_SAMPLES", 2)
    for t in (90.0, 110.0, 150.0, 190.0):  # keeps those at 150 and 190
        count(t, "retry.backoff_lanes", 10)
    assert read_metric("backoff_lanes_per_kop.r2", _run(None)) is None


def _scripted_crash(off_chip):
    """The replicated cell with its crash moved into a short window: MN 0
    is down from the fourth to the ninth window round."""
    load = run.load_cell

    def crash_early(name):
        bench, cell, config, traffic = load(name)
        spec = json.loads(json.dumps(config["store_spec"]))
        ev = spec["faults"]["events"][0]
        ev["at_op"], ev["duration_ops"] = (2 + 3) * 8192, 6 * 8192
        return bench, cell, dict(config, store_spec=spec), traffic

    off_chip.setattr(run, "load_cell", crash_early)


@pytest.mark.parametrize("cell", [R2_CELL, A_CELL])
def test_traced_run(off_chip, capsys, cell):
    """The whole traced run off the chip, at 2^14 pairs: every metric the
    cell lists is read, and the replicated cell fails over and resyncs
    once, hands reads back to the restarted MN 0 and answers every op
    correctly."""
    if cell == R2_CELL:
        _scripted_crash(off_chip)
    program_store, stores = runner.program_store, []

    def keep(spec, keys, values):
        stores.append(program_store(spec, keys, values))
        return stores[-1]

    off_chip.setattr(runner, "program_store", keep)
    out = test_run._result(capsys, cell, seconds=4.0, trace=1)
    assert out["correct"] is True and out["failed"] == 0
    bench = json.loads((test_run.ROOT / "BENCHMARK.json").read_text())
    named = {m["name"] for m in run.cell_metrics(bench, cell, True)}
    # the CPU's trace has no device plane: the device metrics read nothing
    device = {m for m in named if m.startswith(("device_idle_share",
                                                "get_roofline",
                                                "get_device_us_per_kget"))}
    assert set(out["metrics"]) == named - device
    suffix = "r2" if cell == R2_CELL else "ycsb_a"
    # every Get call follows a round's updates, which rewrite the heap
    assert out["metrics"][f"mn_uploads_per_call.{suffix}"]["value"] == 1.0
    if cell == R2_CELL:
        rs = stores[0]
        while not hasattr(rs, "replicas"):
            rs = rs.inner
        m = rs.meter_totals()
        assert (m.failovers, m.resyncs, rs.primary) == (1, 1, 0)
        rounds = out["attempted"] // 8192
        lanes = out["metrics"]["backoff_lanes_per_kop.r2"]["value"]
        assert lanes == pytest.approx(4096 / (rounds * 8192) * 1e3)
        assert all(out["metrics"][m]["value"] > 0 for m in
                   ("fanout_ms_per_kop.r2", "failover_ms.r2",
                    "resync_ms.r2"))


def _resync_installs_nothing(off_chip):
    from repro.core import outback
    off_chip.setattr(outback.OutbackShard, "install_mn_state",
                     lambda self, state: None)


def _rejoined_replica_drops_writes(off_chip):
    """MN 0 acknowledges every write after its restart and applies none."""
    from repro.api import replication
    resync = replication.ReplicaSetAdapter._resync

    def then_drop(self, i):
        done = resync(self, i)
        if done:
            self.replicas[i].engine.update_batch = \
                lambda keys, values: np.ones(len(keys), bool)
        return done

    off_chip.setattr(replication.ReplicaSetAdapter, "_resync", then_drop)


@pytest.mark.parametrize("plant", [_resync_installs_nothing,
                                   _rejoined_replica_drops_writes])
def test_a_restarted_replica_missing_writes_is_not_correct(off_chip, capsys,
                                                           plant):
    """After its restart MN 0 serves the Gets again, so a resync that
    installs nothing, or a fan-out to it that is lost, shows as wrong Gets
    in the check that decides ``correct``."""
    _scripted_crash(off_chip)
    plant(off_chip)
    out = test_run._result(capsys, R2_CELL, seconds=4.0)
    assert out["correct"] is False
    assert out["checks"]["get_wrong"]["value"] > 0
    assert out["checks"]["update_wrong"]["value"] == 0


@pytest.mark.parametrize("plant", [None, _resync_installs_nothing])
def test_readback_of_each_replica(off_chip, capsys, plant):
    """``bench/readback.py`` reads both replicas right after a sound run,
    and finds MN 0 stale where its resync installed nothing."""
    _scripted_crash(off_chip)
    if plant is not None:
        plant(off_chip)
    assert readback.main(["--workload", R2_CELL, "--seed",
                          str(test_run.SEED), "--seconds", "4.0",
                          "--trace", "0"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    out, got = json.loads(lines[-2]), json.loads(lines[-1])["readback"]
    assert (got["failovers"], got["resyncs"], got["primary"]) == (1, 1, 0)
    assert got["keys"] == readback.KEYS and got["outage_keys"] > 0
    if plant is None:
        assert out["correct"] is True
        assert got["wrong"] == [0, 0] and got["mn_state_equal"] is True
    else:
        assert out["correct"] is False
        assert got["wrong"][0] > 0 and got["wrong"][1] == 0
        assert got["mn_state_equal"] is False
