"""The reader of the program's ``get.mn_uploads`` counter, on synthetic
counter samples and on a whole traced run off the chip."""

import sys

import pytest

from lib import runner
import test_run
from run import read_metric
from test_program_spans import _run, counters  # noqa: F401  (fixture)
from test_run import off_chip  # noqa: F401  (fixture)

NAME = "get.mn_uploads"
METRICS = ["mn_uploads_per_call", "mn_uploads_per_call.cn_cache"]


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("window_uploads,want", [
    ([120.0, 180.0], 2 / 2),  # both Get calls of the window re-sent them
    ([150.0], 1 / 2),
    ([], 0.0),  # resident: only the warm-up uploaded
])
def test_uploads_per_get_call(counters, metric, window_uploads, want):
    _, count = counters
    count(50.0, NAME, 1)  # warm-up, before the window
    for t in window_uploads:
        count(t, NAME, 1)
    count(250.0, NAME, 1)  # after the window
    assert read_metric(metric, _run(None)) == pytest.approx(want)


@pytest.mark.parametrize("case", ["never counted", "ring truncated",
                                  "no such module"])
def test_reads_none(counters, monkeypatch, case):
    wall, count = counters
    if case == "ring truncated":
        monkeypatch.setattr(wall, "RING_SAMPLES", 2)
        for t in (90.0, 110.0, 150.0, 190.0):  # keeps those at 150 and 190
            count(t, NAME, 1)
    if case == "no such module":  # a program older than its counters
        count(150.0, NAME, 1)
        monkeypatch.setitem(sys.modules, "repro.obs.wall", None)
        monkeypatch.delattr(sys.modules["repro.obs"], "wall")
    assert read_metric("mn_uploads_per_call", _run(None)) is None


@pytest.mark.parametrize("cell,want", [
    # reads only: the warm-up's first Get uploads, the window none
    (test_run.C_CELL, {"mn_uploads_per_call": 0.0,
                       "h2d_bytes_per_get": 8.0}),
    # every Get call follows a round's updates, which rewrite the heap
    (test_run.B_CELL, {"mn_uploads_per_call.cn_cache": 1.0}),
])
def test_traced_run(off_chip, capsys, cell, want):
    """The whole traced run off the chip (as in test_run.py)."""
    program_store, stores = runner.program_store, []

    def keep(spec, keys, values):
        stores.append(program_store(spec, keys, values))
        return stores[-1]

    off_chip.setattr(runner, "program_store", keep)
    out = test_run._result(capsys, cell, trace=1)
    assert out["correct"] is True
    got = {m: out["metrics"].get(m, {}).get("value") for m in want}
    assert got == want
