"""``chip_smoke.py`` off the chip: it refuses to run without a TPU or
outside a checkout, and its phases pass at a small size on CPU devices.

The phases are called directly with a CPU device; the script itself has
no CPU branch.
"""

import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture
def chip_smoke(monkeypatch):
    monkeypatch.syspath_prepend(str(ROOT))  # benchmarks.common
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, cwd, extra_env=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    out = _run([str(SCRIPT), "--pairs", "4096"], ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_refuses_to_run_outside_a_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(["chip_smoke.py", "--pairs", "4096"], tmp_path)
    assert out.returncode != 0
    assert "src/repro" in out.stderr
    assert '"ok"' not in out.stdout


def test_one_chip_phase_passes_on_cpu(chip_smoke, capsys):
    chip_smoke.run_one_chip(1 << 14, 3, jax.devices()[0])
    out = capsys.readouterr().out
    assert "get_warm_per_call_seconds=" in out
    assert "insert_cases=" in out


def test_one_chip_phase_catches_a_wrong_answer(chip_smoke, monkeypatch):
    # a store that loses one written value must fail the read-back check
    real = chip_smoke.written_value
    calls = []

    def drifting(keys):
        calls.append(len(keys))
        v = real(keys)
        if len(calls) > 2:  # the read-back's expected values
            v = v.copy()
            v[0] ^= 1
        return v

    monkeypatch.setattr(chip_smoke, "written_value", drifting)
    with pytest.raises(SystemExit, match="write read-back: 1 of"):
        chip_smoke.run_one_chip(1 << 14, 3, jax.devices()[0])


@pytest.mark.mesh
def test_four_chip_phase_passes_on_four_cpu_devices():
    code = ("import sys, jax; sys.path[:0] = [sys.argv[1], sys.argv[2]]; "
            "import chip_smoke; "
            "chip_smoke.run_four_chips(1 << 14, 3, jax.devices()[:4]); "
            "print('FOUR_OK')")
    out = _run(["-c", code, str(ROOT / "src"), str(ROOT)], ROOT,
               {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert "FOUR_OK" in out.stdout, out.stderr[-1500:]
