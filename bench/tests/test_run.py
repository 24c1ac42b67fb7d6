"""The whole run off the chip: no result without a TPU, and ``correct``
false under the control and under each fault the cells can have.

The runs here skip the harness's look for a chip and shrink each cell to
2^14 pairs (the cache budget by the same factor); everything else is the
run the chip makes."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

import control
import run
from lib import peaks

ROOT = pathlib.Path(__file__).resolve().parents[2]
C_CELL = "outback-2p24.ycsb-c.uniform"
B_CELL = "outback-cncache-2p23.ycsb-b.zipf99"
PAIRS = 1 << 14
SEED = 2**31 + 101


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_exits_nonzero_without_a_tpu():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", C_CELL,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=_cpu_env(), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", C_CELL,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=_cpu_env(), capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


@pytest.fixture
def off_chip(monkeypatch):
    """Run cells on the CPU at 2^14 pairs."""
    import jax
    load = run.load_cell

    def small(name):
        bench, cell, config, traffic = load(name)
        scale = PAIRS / config["pairs"]
        spec = dict(config["store_spec"])
        if spec["cache_budget_bytes"]:
            spec["cache_budget_bytes"] = int(spec["cache_budget_bytes"] * scale)
        return bench, cell, dict(config, pairs=PAIRS, store_spec=spec), traffic

    monkeypatch.setattr(run, "load_cell", small)
    monkeypatch.setattr(run, "require_chip", lambda chips: jax.devices())
    monkeypatch.setattr(run.peaks, "peaks",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    return monkeypatch


def _result(capsys, cell, seconds=1.0, trace=0, main=run.main):
    assert main(["--workload", cell, "--seed", str(SEED), "--seconds",
                 str(seconds), "--trace", str(trace)]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", [C_CELL, B_CELL])
def test_sound_run_is_correct(off_chip, capsys, cell):
    out = _result(capsys, cell, trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 and c["of"] > 0
               for c in out["checks"].values())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    named = {m["name"] for m in run.cell_metrics(bench, cell, True)}
    compiles = [n for n in named if n.startswith("compiles_in_window")]
    assert compiles and set(compiles) <= set(out["metrics"])
    assert "build_s" in out["metrics"]
    assert set(out["metrics"]) <= named
    assert out["device"]["window_s"] > 0


@pytest.mark.parametrize("cell", [C_CELL, B_CELL])
def test_untraced_run_reports_its_end_to_end_metrics(off_chip, capsys, cell):
    out = _result(capsys, cell)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    named = {m["name"] for m in run.cell_metrics(bench, cell, False)}
    assert set(out["metrics"]) == named
    assert all(m["value"] > 0 for m in out["metrics"].values())


@pytest.mark.parametrize("cell", [C_CELL, B_CELL])
def test_control_is_not_correct(off_chip, capsys, cell):
    out = _result(capsys, cell, main=control.main)
    assert out["correct"] is False
    assert out["checks"]["get_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", [C_CELL, B_CELL])
def test_altered_answer_is_caught(off_chip, capsys, cell):
    from repro.core import outback
    real = outback.outback_get_batch

    def altered(*a, **k):
        v_lo, v_hi, match = real(*a, **k)
        if isinstance(v_lo, np.ndarray):  # the cache fill's host Gets
            return v_lo, v_hi, match
        return v_lo.at[0].set(v_lo[0] ^ 1), v_hi, match

    off_chip.setattr(outback, "outback_get_batch", altered)
    out = _result(capsys, cell)
    assert out["correct"] is False and out["checks"]["get_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", [C_CELL, B_CELL])
def test_half_batch_left_out_is_caught(off_chip, capsys, cell):
    from repro.api import adapters
    real = adapters.OutbackShardAdapter.get_batch

    def half(self, keys, xp=np, **k):
        n = len(keys) // 2
        res = real(self, keys[:len(keys) - n], xp, **k)
        res.values = np.concatenate([res.values, np.zeros(n, np.uint64)])
        res.found = np.concatenate([res.found, np.zeros(n, bool)])
        return res

    off_chip.setattr(adapters.OutbackShardAdapter, "get_batch", half)
    out = _result(capsys, cell)
    assert out["correct"] is False and out["checks"]["get_wrong"]["value"] > 0


def test_update_that_leaves_state_unchanged_is_caught(off_chip, capsys):
    from repro.core import outback
    off_chip.setattr(outback.OutbackShard, "update_batch",
                     lambda self, keys, values: np.ones(len(keys), bool))
    out = _result(capsys, B_CELL, seconds=4.0)
    assert out["correct"] is False and out["checks"]["get_wrong"]["value"] > 0
